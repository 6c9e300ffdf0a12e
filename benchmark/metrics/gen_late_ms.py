"""gen_late_ms: 99th percentile of how late the load generator sent a
request after its due time."""

from benchmark.harness import percentile
from benchmark.readers import Window


def read(w: Window):
    return percentile(w.late_ms, 99) if w.late_ms else None
