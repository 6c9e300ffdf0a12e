"""idle_under_host_pct: share of the device's idle time in the window
that an ``es.*`` host span of the program covers, from the profiler
trace."""

from benchmark.host_spans import idle_under_host_pct
from benchmark.readers import Window


def read(w: Window):
    return idle_under_host_pct(w)
