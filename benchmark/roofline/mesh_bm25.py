"""Mesh BM25: the work of ``bm25.py`` (every posting of every query term
read once, as a doc id, a term frequency and the doc's length, 4 bytes
each), summed over every shard of the index. The shard_map programs of
``parallel/mesh.py`` are jitted around their per-chip body, a function
named ``local``, so each chip's trace holds them as ``jit_local(<n>)``
modules. In a BM25 cell no mesh kNN or sparse program runs; the exact
mesh phases, which serve a drain whose re-rank runs out of depth, are
``jit_local`` modules too, so their device time counts here against the
same work, and a window with such fallbacks reads a lower share."""

PREFIXES = ("local",)
ENTRY_BYTES = 12


def work(w):
    return 0.0, float(ENTRY_BYTES * sum(w.data.work(s) for s in w.sent))
