"""device_wait_ms: mean ``device_wait`` span of the shard path: the
summed wall time of a drain's result syncs, the host blocked on the
device."""

from benchmark.readers import Window, shard_key, span_mean_ms


def read(w: Window):
    return span_mean_ms(w, shard_key(w), "device_wait")
