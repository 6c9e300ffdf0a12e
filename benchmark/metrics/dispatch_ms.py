"""dispatch_ms: mean ``device_dispatch`` span of the shard path: a
drain's execution wall time on the host, result transfer included (not
device time)."""

from benchmark.readers import Window, shard_key, span_mean_ms


def read(w: Window):
    return span_mean_ms(w, shard_key(w), "device_dispatch")
