"""qps: answers that came back whole (status 200, no shard failure)
inside the window, over the window's length."""

from benchmark.readers import Window


def read(w: Window) -> float:
    done = sum(1 for r, good in zip(w.extra["records"], w.extra["ok"])
               if good and r[3] <= w.seconds)
    return done / w.seconds
