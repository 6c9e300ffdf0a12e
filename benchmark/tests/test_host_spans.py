"""The host-span readers on synthetic windows: counters before and after
the window, and a trace extract with nested ``es.*`` host spans over
known device-idle gaps, checked against a plain nanosecond timeline."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import host_spans
from benchmark.readers import Window

METRICS = Path(__file__).resolve().parents[1] / "metrics"
TRACE = Path(__file__).parent / "data" / "trace_knn_12ms.json"


def reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def stats(completed, wait_ns, busy_ns, returns, return_ns, gc_ns,
          device_wait):
    return {
        "thread_pool": {"scheduler": {
            "threads": 1, "active": 0, "queue": 0, "completed": completed,
            "wait_time_in_nanos": wait_ns, "busy_time_in_nanos": busy_ns}},
        "http": {"search_return": {"count": returns,
                                   "time_in_nanos": return_ns}},
        "runtime": {"gc": {"collectors": {
            f"gen{g}": {"collection_count": 10 * g + 1,
                        "collection_time_in_nanos": ns}
            for g, ns in enumerate(gc_ns)}}},
        "search_latency": {"classes": {"knn|batch": {"spans": {
            "device_wait": {"count": device_wait[0],
                            "mean_ms": device_wait[1]}}}}},
    }


def window(before, after, window_ns=2e9, trace=None):
    return Window(traffic={}, query_class="knn", seconds=window_ns / 1e9,
                  latencies_ms=[], late_ms=[], stats_before=before,
                  stats_after=after, data=None, sent=[], peaks={},
                  trace=trace, window_ns=window_ns)


def test_counter_readers_take_deltas_over_the_window():
    before = stats(100, 5_000_000, 400_000_000, 7, 1_000_000,
                   (1_000, 2_000, 3_000), (10, 1.5))
    after = stats(300, 45_000_000, 1_400_000_000, 27, 3_000_000,
                  (6_000, 2_000, 20_003_000), (30, 1.0))
    w = window(before, after)
    # 40 ms of waits over 200 tasks
    assert reader("sched_wait_ms")(w) == pytest.approx(0.2)
    # 1 s busy in a 2 s window
    assert reader("sched_busy_pct")(w) == pytest.approx(50.0)
    # 2 ms over 20 hand-backs
    assert reader("http_return_ms")(w) == pytest.approx(0.1)
    # 30 x 1.0 - 10 x 1.5 = 15 ms over 20 spans
    assert reader("device_wait_ms")(w) == pytest.approx(0.75)
    # 5 us + 0 + 20 ms of collections in a 2 s window
    assert reader("gc_pause_pct")(w) == pytest.approx(1.00025)


def test_counter_readers_are_silent_without_the_counters():
    """A program without the counters (the parent commit) reads None."""
    w = window({"search_latency": {}}, {"search_latency": {}})
    for name in ("sched_wait_ms", "sched_busy_pct", "http_return_ms",
                 "device_wait_ms", "gc_pause_pct", "idle_under_host_pct"):
        assert reader(name)(w) is None, name
    # no task completed in the window: no mean wait
    s = stats(5, 10, 10, 1, 1, (0, 0, 0), (0, 0.0))
    assert reader("sched_wait_ms")(window(s, s)) is None
    assert reader("http_return_ms")(window(s, s)) is None


def synthetic_trace():
    """Device busy [100, 300) and [600, 700) of a 1000 ns window; host
    spans: a task [50, 650) holding a drain [120, 560) holding a device
    wait [400, 540); an HTTP read [800, 900) on another thread beside a
    later task [850, 990); one non-es event over everything."""
    return {"devices": [{"name": "/device:TPU:0", "modules": [],
                         "ops": [["op", 100, 200], ["op", 600, 100]]}],
            "host": [["es.sched.task", 50, 600],
                     ["es.batch.drain", 120, 440],
                     ["es.device_wait", 400, 140],
                     ["es.http.read", 800, 100],
                     ["es.sched.task", 850, 140],
                     ["PjitFunction(knn)", 0, 1000]]}


def grid_attribution(trace, window_ns):
    """Idle nanoseconds per innermost es.* span, one nanosecond at a
    time."""
    n = int(window_ns)
    busy = np.zeros(n, bool)
    for dev in trace["devices"][:1]:
        for _n, s, d in dev["ops"] or dev["modules"]:
            busy[int(s): int(s + d)] = True
    out = {}
    spans = [(s, s + d, name) for name, s, d in trace["host"]
             if name.startswith("es.")]
    for t in range(n):
        if busy[t]:
            continue
        open_ = [(s, s - e, name) for s, e, name in spans if s <= t < e]
        name = max(open_)[2] if open_ else "uncovered"
        out[name] = out.get(name, 0) + 1
    return out


def test_attribute_idle_names_the_innermost_span():
    t = synthetic_trace()
    got = host_spans.attribute_idle(t, 1000.0)
    want = grid_attribution(t, 1000.0)
    assert {k: round(v * 1e9) for k, v in got.items()} == want
    # idle [0,100) [300,600) [700,1000): 700 ns; where the read and the
    # later task overlap, the task opened last
    assert want == {"uncovered": 50 + 100 + 10,
                    "es.sched.task": 50 + 40 + 50 + 90,
                    "es.batch.drain": 100 + 20, "es.device_wait": 140,
                    "es.http.read": 50}
    assert list(got)[0] == "es.sched.task"          # largest first


def test_idle_under_host_pct_on_the_synthetic_trace():
    t = synthetic_trace()
    w = window({}, {}, window_ns=1000.0, trace=t)
    assert reader("idle_under_host_pct")(w) == pytest.approx(
        100.0 * (700 - 160) / 700)


def test_attribute_idle_matches_the_grid_on_random_spans():
    rng = np.random.default_rng(11)
    for _ in range(5):
        ops = [["op", float(s), float(d)] for s, d in
               zip(rng.integers(0, 3000, 12), rng.integers(1, 300, 12))]
        host = [[f"es.s{i % 4}", float(s), float(d)] for i, (s, d) in
                enumerate(zip(rng.integers(-100, 3000, 15),
                              rng.integers(1, 800, 15)))]
        t = {"devices": [{"name": "/device:TPU:0", "modules": [],
                          "ops": ops}], "host": host}
        got = host_spans.attribute_idle(t, 3000.0)
        want = grid_attribution(t, 3000.0)
        assert {k: round(v * 1e9) for k, v in got.items()} == want


def test_parent_trace_without_es_spans_reads_none():
    """The recorded v5e extract predates the es.* annotations: the
    reader has nothing to read there and says so."""
    t = json.loads(TRACE.read_text())
    w = window({}, {}, window_ns=float(t["window_ns"]), trace=t)
    assert reader("idle_under_host_pct")(w) is None
    idle = host_spans.attribute_idle(t, float(t["window_ns"]))
    assert set(idle) == {"uncovered"}
