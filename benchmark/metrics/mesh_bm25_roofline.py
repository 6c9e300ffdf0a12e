"""mesh_bm25_roofline: least time of the window's BM25 work, over the
peaks of every traced chip together, against the mean device time of the
mesh BM25 programs on a chip."""

from benchmark.readers import Window, roofline_pct
from benchmark.roofline import mesh_bm25


def read(w: Window):
    if w.trace is None:
        return None
    return roofline_pct(w, mesh_bm25, chips=len(w.trace["devices"]))
