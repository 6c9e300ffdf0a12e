"""sched_wait_ms: mean wait of a task on the node's dispatch thread
(start minus due time, ``thread_pool.scheduler``) over the window."""

from benchmark.host_spans import per_event_ms
from benchmark.readers import Window

SCHED = ("thread_pool", "scheduler")


def read(w: Window):
    return per_event_ms(w, SCHED + ("wait_time_in_nanos",),
                        SCHED + ("completed",))
