"""sched_busy_pct: share of the window the node's dispatch thread spent
running tasks (``thread_pool.scheduler`` busy time)."""

from benchmark.host_spans import delta, window_pct
from benchmark.readers import Window


def read(w: Window):
    return window_pct(w, delta(w, "thread_pool", "scheduler",
                               "busy_time_in_nanos"))
