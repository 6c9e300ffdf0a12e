"""gc_pause_pct: share of the window the process spent in garbage
collection, every generation summed (``runtime.gc.collectors``)."""

from benchmark.host_spans import gc_pause_ns, window_pct
from benchmark.readers import Window


def read(w: Window):
    return window_pct(w, gc_pause_ns(w))
