"""knn_roofline: least time of the window's exact kNN work over the
device time of the kNN kernel family."""

from benchmark.readers import Window, roofline_pct
from benchmark.roofline import knn


def read(w: Window):
    return roofline_pct(w, knn)
