"""http_return_ms: mean hand-back of a search response from the
scheduler thread's completion to the HTTP event loop
(``http.search_return``)."""

from benchmark.host_spans import per_event_ms
from benchmark.readers import Window

RETURN = ("http", "search_return")


def read(w: Window):
    return per_event_ms(w, RETURN + ("time_in_nanos",), RETURN + ("count",))
