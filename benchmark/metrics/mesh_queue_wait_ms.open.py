"""mesh_queue_wait_ms.open: mean ``queue_wait`` span of the mesh
executor under open-loop arrivals: a search's wait from its submission
to the start of the drain that serves it."""

from benchmark.readers import Window, mesh_key, span_mean_ms


def read(w: Window):
    return span_mean_ms(w, mesh_key(w), "queue_wait")
