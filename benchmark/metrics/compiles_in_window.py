"""compiles_in_window: programs the device observatory saw compile (or
load from the persistent cache) inside the window."""

from benchmark.readers import Window, compiles_delta


def read(w: Window) -> float:
    return float(compiles_delta(w))
