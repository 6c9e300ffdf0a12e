"""device_idle_pct.open: share of the window in which no operation ran
on the device, from the profiler trace."""

from benchmark.readers import Window, idle_pct


def read(w: Window):
    return idle_pct(w)
