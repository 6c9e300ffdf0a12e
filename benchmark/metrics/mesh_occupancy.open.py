"""mesh_occupancy.open: searches per mesh drain over the window
(``mesh_plane`` mesh_searches / mesh_batches)."""

from benchmark.readers import Window, mesh_delta


def read(w: Window):
    batches = mesh_delta(w, "mesh_batches")
    if batches <= 0:
        return None
    return mesh_delta(w, "mesh_searches") / batches
