"""BM25: every posting of every query term is read once, as a doc id, a
term frequency and the doc's length (4 bytes each)."""

PREFIXES = ("bm25",)
ENTRY_BYTES = 12


def work(w):
    return 0.0, float(ENTRY_BYTES * sum(w.data.work(s) for s in w.sent))
