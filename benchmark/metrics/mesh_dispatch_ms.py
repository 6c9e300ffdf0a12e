"""mesh_dispatch_ms: mean ``device_dispatch`` span of the mesh executor:
a mesh drain's wall time on the host, from its start to its per-shard
results (every mesh program of the drain and its result transfers; not
device time)."""

from benchmark.readers import Window, mesh_key, span_mean_ms


def read(w: Window):
    return span_mean_ms(w, mesh_key(w), "device_dispatch")
