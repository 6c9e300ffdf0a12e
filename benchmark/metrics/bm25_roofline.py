"""bm25_roofline: least time of the window's BM25 work over the
device time of the BM25 kernel family."""

from benchmark.readers import Window, roofline_pct
from benchmark.roofline import bm25


def read(w: Window):
    return roofline_pct(w, bm25)
