"""queue_wait_ms.serial: mean ``queue_wait`` span of the shard batcher
with one client."""

from benchmark.readers import Window, shard_key, span_mean_ms


def read(w: Window):
    return span_mean_ms(w, shard_key(w), "queue_wait")
