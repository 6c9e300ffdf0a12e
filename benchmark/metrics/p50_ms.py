"""p50_ms: median latency of every request of the window, from its due
time (open loop) or its send (closed loop); a failed one is slowest."""

from benchmark.harness import percentile
from benchmark.readers import Window


def read(w: Window) -> float:
    return percentile(w.latencies_ms, 50)
