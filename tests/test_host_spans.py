"""Host spans on the profiler clock, and the counters behind them.

Every host-side region goes through ``telemetry.region`` (a
``jax.profiler`` annotation ``es.<name>`` plus, given a trace, a span of
it); result syncs of the drain paths go through ``telemetry.to_host``
(``es.device_wait`` plus one ``device_wait`` span per batch member).
Under test: the scheduler's ``thread_pool.scheduler`` counters, the
``runtime.gc`` collectors, the REST ``http.search_return`` hand-back,
the ``device_wait`` span of plane BM25 and one-segment kNN drains, and
the ``es.*`` events a served search leaves in a profiler trace.
"""

import asyncio
import copy
import gc
import http.client
import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.search import telemetry
from elasticsearch_tpu.search.telemetry import TELEMETRY, SearchTrace
from elasticsearch_tpu.testing import InProcessCluster
from elasticsearch_tpu.transport.scheduler import ThreadedScheduler

pytestmark = pytest.mark.telemetry


def _ok(resp, err):
    assert err is None, f"unexpected error: {err}"
    return resp


# ---------------------------------------------------------------------------
# the span helpers
# ---------------------------------------------------------------------------

def test_region_records_span_with_dispatches_and_runs_without_trace():
    trace = SearchTrace("bm25", "solo")
    with telemetry.activate(trace):
        with telemetry.region("rewrite", trace, generation=3) as t:
            assert t is trace
            telemetry.record_dispatch(2)
        with telemetry.region("merge"):          # annotation only
            pass
    names = [n for n, _d, _m in trace.spans]
    assert names == ["rewrite"]
    assert trace.spans[0][2] == {"dispatches": 2}
    assert trace.span_ns("rewrite") >= 1


def test_to_host_matches_asarray_and_counts_syncs():
    a = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    b = jnp.array([1, 2], jnp.int32)
    trace = SearchTrace("knn", "batch")
    with telemetry.activate(trace):
        one = telemetry.to_host(a)
        x, y = telemetry.to_host(a, b)
    for got, want in ((one, a), (x, a), (y, b)):
        assert isinstance(got, np.ndarray)
        assert got.dtype == np.asarray(want).dtype
        assert np.array_equal(got, np.asarray(want))
    assert trace.syncs == 2 and trace.wait_ns > 0
    # outside any trace it only fetches
    assert np.array_equal(telemetry.to_host(b), np.asarray(b))


# ---------------------------------------------------------------------------
# scheduler and GC counters
# ---------------------------------------------------------------------------

def test_scheduler_counts_busy_and_wait_time():
    s = ThreadedScheduler()
    try:
        before = s.stats()
        for _ in range(2):
            s.submit(lambda: time.sleep(0.05))
        s.submit(lambda: None)       # queued behind both sleeps
        deadline = time.monotonic() + 10
        while s.stats()["completed"] < before["completed"] + 3:
            assert time.monotonic() < deadline, "tasks did not complete"
            time.sleep(0.01)
        after = s.stats()
        assert after["busy_time_in_nanos"] - \
            before["busy_time_in_nanos"] >= 100_000_000
        # the second sleep waited behind the first, the third task
        # behind both
        assert after["wait_time_in_nanos"] - \
            before["wait_time_in_nanos"] >= 100_000_000
        assert after["threads"] == 1 and after["queue"] == 0
    finally:
        s.close()


def test_gc_collect_raises_gen2_count_and_time():
    from elasticsearch_tpu.monitor import GC, runtime_stats
    GC.install()
    GC.install()                     # once per process
    assert gc.callbacks.count(GC._on_gc) == 1
    before = runtime_stats()["gc"]["collectors"]["gen2"]
    junk = [[i] for i in range(20_000)]
    gc.collect()
    del junk
    after = runtime_stats()["gc"]["collectors"]["gen2"]
    assert after["collection_count"] >= before["collection_count"] + 1
    assert after["collection_time_in_nanos"] > \
        before["collection_time_in_nanos"]


# ---------------------------------------------------------------------------
# device_wait spans of the drain paths
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    """One node: "hb" (1 shard, 2 segments of 700 docs — the packed
    plane and its bf16 coarse tier) and "hk" (1 shard, 1 segment of
    vectors — the per-segment kNN path)."""
    c = InProcessCluster(n_nodes=1, seed=71)
    c.start()
    client = c.client()
    rng = np.random.default_rng(71)
    vocab = [f"w{i}" for i in range(40)]
    _ok(*c.call(lambda cb: client.create_index("hb", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"body": {"type": "text"}}}}, cb)))
    _ok(*c.call(lambda cb: client.create_index("hk", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"vec": {
            "type": "dense_vector", "dims": 8,
            "similarity": "l2_norm"}}}}, cb)))
    c.ensure_green("hb")
    c.ensure_green("hk")
    for d in range(700):
        _ok(*c.call(lambda cb, d=d: client.index_doc("hb", f"d{d}", {
            "body": " ".join(rng.choice(
                vocab, size=int(rng.integers(4, 12))))}, cb)))
        if d == 350:
            c.call(lambda cb: client.refresh("hb", cb))
    c.call(lambda cb: client.refresh("hb", cb))
    for d in range(64):
        _ok(*c.call(lambda cb, d=d: client.index_doc("hk", f"v{d}", {
            "vec": [float(x) for x in rng.standard_normal(8)]}, cb)))
    c.call(lambda cb: client.refresh("hk", cb))
    yield c
    c.stop()


def _wave_traces(c, index, bodies, monkeypatch):
    """Serve ``bodies`` concurrently; returns the batch-member traces
    the drains handed to the latency histograms."""
    seen = []
    observe = TELEMETRY.observe

    def capture(trace):
        seen.append(trace)
        observe(trace)

    monkeypatch.setattr(TELEMETRY, "observe", capture)
    client = c.client()
    boxes = []
    for b in bodies:
        box = []
        client.search(index, copy.deepcopy(b),
                      lambda resp, err=None, box=box: box.append(
                          (resp, err)))
        boxes.append(box)
    c.run_until(lambda: all(boxes), 120.0)
    for box in boxes:
        _ok(*box[0])
    monkeypatch.setattr(TELEMETRY, "observe", observe)
    return [t for t in seen if t.data_plane == "batch"]


def _one_device_wait(trace):
    waits = [(d, m) for n, d, m in trace.spans if n == "device_wait"]
    assert len(waits) == 1, trace.spans
    dur, meta = waits[0]
    assert meta["syncs"] >= 1 and dur >= 1
    assert dur <= trace.span_ns("device_dispatch")


def test_plane_bm25_drain_gives_each_member_one_device_wait(cluster,
                                                             monkeypatch):
    bodies = [{"query": {"match": {"body": q}}, "size": 5}
              for q in ("w1 w3", "w2 w7 w9", "w4")]
    members = _wave_traces(cluster, "hb", bodies, monkeypatch)
    assert len(members) == len(bodies)
    for t in members:
        assert t.query_class == "bm25" and t.plane_backed
        _one_device_wait(t)


def test_one_segment_knn_drain_gives_each_member_one_device_wait(
        cluster, monkeypatch):
    rng = np.random.default_rng(5)
    bodies = [{"query": {"knn": {
        "field": "vec", "k": 3, "num_candidates": 10,
        "query_vector": [float(x) for x in rng.standard_normal(8)]}},
        "size": 3} for _ in range(3)]
    members = _wave_traces(cluster, "hk", bodies, monkeypatch)
    assert len(members) == len(bodies)
    for t in members:
        assert t.query_class == "knn" and not t.plane_backed
        _one_device_wait(t)


# ---------------------------------------------------------------------------
# served over HTTP: the hand-back counter and the profiler's es.* events
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One node on the threaded scheduler with its HTTP server on an
    event-loop thread, and "hv" (1 shard, 1 segment of vectors)."""
    from elasticsearch_tpu.rest.server import HttpServer, boot_single_node
    node = boot_single_node()
    server = HttpServer(node.client, "127.0.0.1", 0)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(server.start())
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    assert started.wait(60)
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)

    def call(method, path, body=None):
        conn.request(method, path,
                     body=None if body is None else json.dumps(body),
                     headers={"content-type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"null")

    assert call("PUT", "/hv", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"vec": {
            "type": "dense_vector", "dims": 4,
            "similarity": "l2_norm"}}}})[0] == 200
    rng = np.random.default_rng(3)
    for d in range(16):
        status, _ = call("PUT", f"/hv/_doc/{d}", {
            "vec": [float(x) for x in rng.standard_normal(4)]})
        assert status == 201
    assert call("POST", "/hv/_refresh")[0] == 200
    knn = {"query": {"knn": {"field": "vec", "k": 2, "num_candidates": 8,
                             "query_vector": [0.1, 0.2, 0.3, 0.4]}},
           "size": 2}
    assert call("POST", "/hv/_search", knn)[0] == 200     # compiles here
    yield node, call, knn
    conn.close()
    asyncio.run_coroutine_threadsafe(server.stop(), loop).result(30)
    loop.call_soon_threadsafe(loop.stop)
    thread.join(30)
    node.stop()


def test_served_search_raises_search_return_count_by_one(served):
    node, call, knn = served

    def search_return():
        stats = node.local_node_stats(sections=["http", "thread_pool"])
        assert stats["thread_pool"]["scheduler"]["completed"] > 0
        return stats["http"]["search_return"]

    before = search_return()
    assert call("GET", "/_cluster/health")[0] == 200     # not a search
    assert search_return() == before
    status, body = call("POST", "/hv/_search", knn)
    assert status == 200 and len(body["hits"]["hits"]) == 2
    after = search_return()
    assert after["count"] == before["count"] + 1
    assert after["time_in_nanos"] > before["time_in_nanos"]


def test_profiler_trace_holds_es_spans_with_drain_inside_task(served,
                                                              tmp_path):
    from benchmark import trace_reduce
    _node, call, knn = served
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        status, _ = call("POST", "/hv/_search", knn)
        # the connection reads its next request only once the search's
        # response write has returned: its es.http.write span is closed
        call("GET", "/_cluster/health")
    finally:
        jax.profiler.stop_trace()
    assert status == 200
    host = trace_reduce.load(str(tmp_path))["host"]
    by_name = {}
    for name, start, dur in host:
        by_name.setdefault(name, []).append((start, start + dur))
    for name in ("es.http.read", "es.http.write", "es.sched.task",
                 "es.batch.drain", "es.device_wait"):
        assert name in by_name, sorted(n for n in by_name
                                       if n.startswith("es."))
    assert any(t0 <= d0 and d1 <= t1
               for d0, d1 in by_name["es.batch.drain"]
               for t0, t1 in by_name["es.sched.task"])
    assert any(d0 <= w0 and w1 <= d1
               for w0, w1 in by_name["es.device_wait"]
               for d0, d1 in by_name["es.batch.drain"])
