"""setup_s: from process start until the window opened."""

from benchmark.readers import Window


def read(w: Window) -> float:
    return w.extra["setup_s"]
