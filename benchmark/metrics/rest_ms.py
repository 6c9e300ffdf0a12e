"""rest_ms: mean time from send to answer at the generator, minus the
coordinator's own mean total per query (``search_latency`` of the
class's ``fanout`` key): the REST layer's parse, dispatch hops and JSON
encode, plus the loopback socket."""

from benchmark.readers import Window, coordinator_key, mean, span_mean_ms


def read(w: Window):
    coord = span_mean_ms(w, coordinator_key(w), "total")
    lat = mean([1e3 * (r[3] - r[2]) for r, good
                in zip(w.extra["records"], w.extra["ok"]) if good])
    if coord is None or lat is None:
        return None
    return lat - coord
