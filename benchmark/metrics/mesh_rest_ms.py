"""mesh_rest_ms: ``rest_ms`` where the mesh program serves the fan-out:
mean time from send to answer at the generator, minus the coordinator's
own mean total per query, which a mesh-served search records under the
class's ``mesh_plane`` key (``search_latency``): the REST layer's parse,
dispatch hops and JSON encode, plus the loopback socket."""

from benchmark.readers import Window, mean, span_mean_ms


def read(w: Window):
    coord = span_mean_ms(w, f"{w.query_class}|mesh_plane", "total")
    lat = mean([1e3 * (r[3] - r[2]) for r, good
                in zip(w.extra["records"], w.extra["ok"]) if good])
    if coord is None or lat is None:
        return None
    return lat - coord
