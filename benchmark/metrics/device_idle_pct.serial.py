"""device_idle_pct.serial: the same with one client."""

from benchmark.readers import Window, idle_pct


def read(w: Window):
    return idle_pct(w)
