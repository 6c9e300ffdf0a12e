"""mesh_served_pct: share of the window's requests that the mesh program
served (``mesh_plane`` mesh_searches over the requests sent): below 100,
some fan-outs went over the per-shard RPC path instead."""

from benchmark.readers import Window, mesh_delta


def read(w: Window):
    if not w.sent:
        return None
    return 100.0 * mesh_delta(w, "mesh_searches") / len(w.sent)
