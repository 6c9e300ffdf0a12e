"""Search telemetry plane: invisibility, span trees, histograms, taxonomy.

The telemetry layer (search/telemetry.py) must be byte-invisible with
``profile`` off — responses on every data plane (solo / batch / plane /
mesh) carry no telemetry keys and repeat identically while the
histograms record — while ``"profile": true`` returns the full span
tree per shard plus the coordinator's, ``_nodes/stats`` serves the
``"search_latency"`` histograms, every routing decision / fallback
carries a TYPED reason (the "unknown" bucket stays at zero), in-flight
searches show their phase + chosen plane in ``GET /_tasks``, requests
with a [timeout] budget are mesh-eligible, a started node serves its
first mesh-eligible search from the mesh, and ``_cat/indices``
resolves every index's health in ONE master round trip.
"""

import copy
import json
import os

import numpy as np
import pytest

from elasticsearch_tpu.ops.device_segment import MESH_PLANES, PLANES
from elasticsearch_tpu.search import telemetry
from elasticsearch_tpu.search.telemetry import (
    KNOWN_REASONS, TELEMETRY, SearchTrace,
)
from elasticsearch_tpu.testing import InProcessCluster

CHAOS_SEEDS = int(os.environ.get("CHAOS_SEEDS", "1") or "1")

pytestmark = pytest.mark.telemetry


def _ok(resp, err):
    assert err is None, f"unexpected error: {err}"
    return resp


@pytest.fixture(autouse=True)
def _defaults():
    """Registries are process-global (the BREAKERS precedent): every
    test starts from default config; the telemetry registry is NOT
    reset here — tests that need clean counters snapshot deltas."""
    for reg in (MESH_PLANES, PLANES):
        reg.enabled = True
    MESH_PLANES.min_shards = 2
    MESH_PLANES.dp = 1
    MESH_PLANES.max_devices = 0
    PLANES.min_segments = 2
    yield


@pytest.fixture(scope="module")
def cluster():
    """One node, two indices: "tm" (3 shards — the mesh-served fan-out)
    and "ts" (1 shard, >= 2 segments — batch / plane / solo)."""
    c = InProcessCluster(n_nodes=1, seed=53)
    c.start()
    client = c.client()
    rng = np.random.default_rng(53)
    vocab = [f"w{i}" for i in range(30)]
    for name, shards in (("tm", 3), ("ts", 1)):
        _ok(*c.call(lambda cb, n=name, s=shards: client.create_index(
            n, {"settings": {"number_of_shards": s,
                             "number_of_replicas": 0},
                "mappings": {"properties": {
                    "body": {"type": "text"},
                    "vec": {"type": "dense_vector", "dims": 8,
                            "similarity": "cosine"},
                    "feats": {"type": "rank_features"},
                    "tag": {"type": "keyword"}}}}, cb)))
        c.ensure_green(name)
        for d in range(90):
            _ok(*c.call(lambda cb, n=name, d=d: client.index_doc(
                n, f"d{d}", {
                    "body": " ".join(rng.choice(
                        vocab, size=int(rng.integers(4, 12)))),
                    "vec": [float(x) for x in rng.standard_normal(8)],
                    "feats": {f"f{j}": float(rng.random() + 0.1)
                              for j in rng.integers(0, 12, 3)},
                    "tag": f"t{d % 3}"}, cb)))
            if d == 45:
                c.call(lambda cb, n=name: client.refresh(n, cb))
        c.call(lambda cb, n=name: client.refresh(n, cb))
    # backend first-init on the RPC path (the mesh never pays it)
    c.call(lambda cb: client.search(
        "tm", {"query": {"match": {"body": "w0"}}, "size": 1}, cb))
    yield c
    c.stop()


def _bodies(rng):
    return [
        {"query": {"match": {"body": "w1 w3 w7"}}, "size": 6},
        {"query": {"knn": {"field": "vec", "k": 5, "query_vector":
                           [float(x) for x in rng.standard_normal(8)]}},
         "size": 5},
        {"query": {"text_expansion": {"feats": {"tokens":
                                                {"f1": 1.2, "f4": 0.7}}}},
         "size": 5},
    ]


def _search(c, index, body):
    client = c.client()
    return _ok(*c.call(lambda cb: client.search(
        index, copy.deepcopy(body), cb)))


def _wave(c, index, bodies):
    client = c.client()
    boxes = []
    for b in bodies:
        box = []
        client.search(index, copy.deepcopy(b),
                      lambda resp, err=None, box=box: box.append(
                          (resp, err)))
        boxes.append(box)
    c.run_until(lambda: all(boxes), 120.0)
    return [_ok(*box[0]) for box in boxes]


def _set(c, settings):
    client = c.client()
    _ok(*c.call(lambda cb: client.cluster_update_settings(
        {"persistent": settings}, cb)))


# telemetry-only key names that must NEVER appear in a profile-off
# response on any path
_FORBIDDEN = ('"telemetry"', '"queue_wait"', '"device_dispatch"',
              '"query_class"', '"phases"', '"span"', '"device_wait"',
              '"syncs"')


# ---------------------------------------------------------------------------
# byte-invisibility: profile off => no telemetry keys, repeat-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [5 + 311 * k for k in range(CHAOS_SEEDS)])
def test_profile_off_byte_invisibility_all_planes(cluster, seed, tmp_path):
    import jax
    c = cluster
    rng = np.random.default_rng(seed)
    bodies = _bodies(rng)
    # mesh (3-shard fan-out), batch (concurrent 1-shard wave), and the
    # solo/plane paths (batch disabled) — each serialized response must
    # carry zero telemetry keys and repeat byte-identically while the
    # histograms record in between, and while a profiler session records
    # the es.* host spans
    for index, plane in (("tm", "mesh"), ("ts", "batch")):
        first = _wave(c, index, bodies)
        TELEMETRY.snapshot()           # recording mustn't perturb state
        second = _wave(c, index, bodies)
        jax.profiler.start_trace(str(tmp_path / plane))
        try:
            traced = _wave(c, index, bodies)
        finally:
            jax.profiler.stop_trace()
        for body, a, b in zip(bodies * 2, first * 2, second + traced):
            raw = json.dumps(a, sort_keys=True)
            for key in _FORBIDDEN:
                assert key not in raw, (plane, body, key)
            sa = {k: v for k, v in a.items() if k != "took"}
            sb = {k: v for k, v in b.items() if k != "took"}
            assert json.dumps(sa, sort_keys=True) == \
                json.dumps(sb, sort_keys=True), (plane, body)
    _set(c, {"search.batch.enabled": False,
             "search.mesh.enabled": False})
    try:
        for body in bodies:
            resp = _search(c, "ts", body)
            raw = json.dumps(resp, sort_keys=True)
            for key in _FORBIDDEN:
                assert key not in raw, ("solo", body, key)
    finally:
        _set(c, {"search.batch.enabled": None,
                 "search.mesh.enabled": None})


# ---------------------------------------------------------------------------
# profile on: the span tree per shard + the coordinator's
# ---------------------------------------------------------------------------

def test_profile_span_tree_shape(cluster):
    c = cluster
    resp = _search(c, "ts", {"query": {"match": {"body": "w1 w3"}},
                             "size": 5, "profile": True})
    shards = resp["profile"]["shards"]
    assert shards, "profile block lost its shard entries"
    tel = shards[0]["searches"][0]["telemetry"]
    assert tel["query_class"] == "bm25"
    # every shard query is a batch member now; profile rides the
    # per-member dense kind, which its trace names "dense"
    assert tel["data_plane"] == "dense"
    names = [p["name"] for p in tel["phases"]]
    for phase in ("queue_wait", "rewrite", "device_dispatch", "demux"):
        assert phase in names, names
    assert all(p["time_in_nanos"] >= 1 for p in tel["phases"])
    assert tel["device_dispatches"] >= 1
    assert tel["time_in_nanos"] >= 1
    # the coordinator's request-level trace rides the same block
    coord = resp["profile"]["coordinator"]
    cnames = [p["name"] for p in coord["phases"]]
    for phase in ("rewrite", "can_match", "query_phase", "merge"):
        assert phase in cnames, cnames
    assert coord["data_plane"] == "fanout"

    # the mesh-served fan-out keeps the existing per-shard profile
    # surface (profile is mesh-ineligible: each shard query rides the
    # batcher's dense kind, so the span tree is the member's)
    resp = _search(c, "tm", {"query": {"match": {"body": "w1"}},
                             "size": 5, "profile": True})
    assert len(resp["profile"]["shards"]) == 3
    for sh in resp["profile"]["shards"]:
        assert "telemetry" in sh["searches"][0]


# ---------------------------------------------------------------------------
# every query class on every data plane: traces with the right spans
# ---------------------------------------------------------------------------

def test_every_class_every_plane_produces_traces(cluster):
    c = cluster
    TELEMETRY.reset()
    rng = np.random.default_rng(7)
    bodies = _bodies(rng)
    hybrid = {"size": 5, "query": {"match": {"body": "w0 w3"}},
              "knn": {"field": "vec", "k": 7,
                      "query_vector": [0.1 * j - 0.3 for j in range(8)]},
              "rank": {"rrf": {"rank_window_size": 15}}}

    _wave(c, "tm", bodies)         # mesh
    _wave(c, "ts", bodies)         # batch (concurrent wave coalesces)
    _wave(c, "ts", [hybrid])       # hybrid coordinator trace
    # the shard batcher is THE transport execution path now; the
    # embedded single-shard SearchService keeps the solo label (and the
    # plane relabel when the shard's plane is resident), so drive it
    # directly for those planes
    from elasticsearch_tpu.search.service import SearchService
    engine = c.nodes["node0"].search_transport.indices.shard(
        "ts", 0).engine
    svc = SearchService(engine, "ts")
    for b in bodies:
        svc.search(copy.deepcopy(b))   # plane (>= 2 segments, plane on)
    _set(c, {"search.plane.enabled": False})
    try:
        _search(c, "ts", bodies[0])    # applies plane config process-wide
        for b in bodies:
            svc.search(copy.deepcopy(b))   # solo (plane off)
    finally:
        _set(c, {"search.plane.enabled": None})
        _search(c, "ts", bodies[0])

    snap = TELEMETRY.snapshot()
    classes = snap["classes"]
    for cls in ("bm25", "knn", "sparse"):
        for plane in ("mesh", "batch", "solo"):
            key = f"{cls}|{plane}"
            assert key in classes, (key, sorted(classes))
            entry = classes[key]
            assert entry["queries"] >= 1
            assert entry["latency"]["count"] >= 1
            spans = ("device_dispatch",) if plane == "solo" \
                else ("queue_wait", "device_dispatch")
            for span in spans:
                assert span in entry["spans"], (key, entry["spans"])
                assert entry["spans"][span]["count"] >= 1
    # the plane-backed embedded path relabels to the "plane" data plane
    assert any(k.endswith("|plane") for k in classes), sorted(classes)
    # mesh/batch traces carry real device-dispatch counts
    assert classes["bm25|mesh"]["device_dispatches"] >= 1
    assert classes["bm25|batch"]["device_dispatches"] >= 1
    # the hybrid request records at the coordinator with its legs/fusion
    assert "hybrid|fanout" in classes
    hspans = classes["hybrid|fanout"]["spans"]
    assert "legs" in hspans and "fuse" in hspans
    # the whole run produced zero untyped fallbacks
    assert snap["fallback_reasons"].get("unknown", 0) == 0
    assert set(snap["fallback_reasons"]) <= KNOWN_REASONS


# ---------------------------------------------------------------------------
# _nodes/stats "search_latency" + the typed fallback taxonomy
# ---------------------------------------------------------------------------

def test_nodes_stats_search_latency_surface(cluster):
    c = cluster
    _wave(c, "tm", _bodies(np.random.default_rng(3)))
    node = c.nodes["node0"]
    sl = node.local_node_stats()["search_latency"]
    assert sl["classes"], "search_latency section empty after searches"
    entry = next(iter(sl["classes"].values()))
    for field in ("queries", "device_dispatches", "latency", "spans"):
        assert field in entry
    lat = entry["latency"]
    for pct in ("p50_ms", "p95_ms", "p99_ms", "mean_ms", "count"):
        assert pct in lat
    assert sl["fallback_reasons"].get("unknown", 0) == 0
    assert set(sl["fallback_reasons"]) <= KNOWN_REASONS


def test_typed_fallback_reasons_for_routing_decisions(cluster):
    c = cluster
    before = dict(TELEMETRY.fallbacks)
    _set(c, {"search.mesh.enabled": False})
    try:
        _search(c, "tm", {"query": {"match": {"body": "w1"}}, "size": 3})
    finally:
        _set(c, {"search.mesh.enabled": None})
    assert TELEMETRY.fallbacks.get("mesh_disabled", 0) > \
        before.get("mesh_disabled", 0)
    # a single-shard fan-out records the too-few-shards decision
    before = dict(TELEMETRY.fallbacks)
    _set(c, {"search.batch.enabled": False})
    try:
        _search(c, "ts", {"query": {"match": {"body": "w1"}}, "size": 3})
    finally:
        _set(c, {"search.batch.enabled": None})
    assert TELEMETRY.fallbacks.get("mesh_too_few_shards", 0) > \
        before.get("mesh_too_few_shards", 0)
    assert TELEMETRY.fallbacks.get("unknown", 0) == 0


def test_batch_drain_failure_counts_typed_reason(cluster, monkeypatch):
    """A shared-drain failure degrades to the occupancy-1 re-drain lane
    AND counts under a typed reason — never a bare or unknown count."""
    c = cluster
    sts = c.nodes["node0"].search_transport
    batcher = sts.batcher
    before = TELEMETRY.fallbacks.get("batch_exec_error", 0)

    orig = batcher._execute

    def boom(key, live):
        if len(live) > 1:        # the shared drain fails; the
            raise RuntimeError("injected batch failure")
        return orig(key, live)   # occupancy-1 re-drain succeeds
    monkeypatch.setattr(batcher, "_execute", boom)
    reqs = [{"index": "ts", "shard": 0, "window": 5,
             "body": {"query": {"match": {"body": f"w{i}"}}}}
            for i in range(3)]
    deferreds = [batcher.enqueue(r) for r in reqs]
    assert all(d is not None for d in deferreds)
    results = [None] * len(reqs)
    for i, d in enumerate(deferreds):
        d._subscribe(lambda v, i=i: results.__setitem__(i, ("ok", v)),
                     lambda e, i=i: results.__setitem__(i, ("err", e)))
    key = next(k for k, q in batcher._queues.items() if q)
    batcher._drain(key)
    assert all(r is not None and r[0] == "ok" for r in results), results
    assert TELEMETRY.fallbacks["batch_exec_error"] == before + 3
    assert TELEMETRY.fallbacks.get("unknown", 0) == 0


def test_mesh_plane_missing_counts_typed_reason(cluster, monkeypatch):
    c = cluster
    before = TELEMETRY.fallbacks.get("mesh_plane_missing", 0)
    monkeypatch.setattr(MESH_PLANES, "get", lambda *a, **kw: None)
    resp = _search(c, "tm", {"query": {"match": {"body": "w2"}},
                             "size": 4})
    assert resp.get("_data_plane") == "batch"      # served by the fan-out
    assert resp["hits"]["hits"] is not None
    assert TELEMETRY.fallbacks["mesh_plane_missing"] > before
    assert TELEMETRY.fallbacks.get("unknown", 0) == 0


def test_unknown_reason_maps_to_unknown_bucket():
    """count_fallback maps unrecognized reasons to "unknown" — the
    bucket every surface test pins at zero, so an untyped call site
    fails CI loudly instead of hiding in a bare count."""
    before = TELEMETRY.fallbacks.get("unknown", 0)
    TELEMETRY.count_fallback("some_brand_new_untyped_reason")
    assert TELEMETRY.fallbacks["unknown"] == before + 1
    # undo: the taxonomy tests pin unknown at zero
    TELEMETRY.fallbacks["unknown"] = before
    if not before:
        TELEMETRY.fallbacks.pop("unknown", None)


# ---------------------------------------------------------------------------
# in-flight _tasks phase visibility
# ---------------------------------------------------------------------------

def test_tasks_show_phase_and_data_plane_in_flight(cluster):
    c = cluster
    sts = c.nodes["node0"].search_transport
    batcher = sts.batcher
    req = {"index": "ts", "shard": 0, "window": 5,
           "body": {"query": {"match": {"body": "w1 w2"}}}}
    deferred = batcher.enqueue(dict(req))
    assert deferred is not None
    member = next(m for q in batcher._queues.values() for m in q)
    # queued members are visible as such before the drain
    assert member.task is not None
    assert member.task.status == {"phase": "queued",
                                  "data_plane": "batch"}
    task_view = member.task.to_dict()
    assert task_view["status"]["phase"] == "queued"
    got = []
    deferred._subscribe(lambda v: got.append(v),
                        lambda e: got.append(e))
    key = next(k for k, q in batcher._queues.items() if q)
    batcher._drain(key)
    assert got and isinstance(got[0], dict)


# ---------------------------------------------------------------------------
# mesh deadline eligibility ([timeout] budgets ride the mesh now)
# ---------------------------------------------------------------------------

def test_timeout_budget_requests_are_mesh_eligible(cluster):
    c = cluster
    body = {"query": {"match": {"body": "w1 w3"}}, "size": 6,
            "timeout": "30s"}
    resp = _search(c, "tm", body)
    assert resp.get("_data_plane") == "mesh_plane", \
        "a [timeout] fan-out must ride the mesh now"
    assert resp["timed_out"] is False
    # identical hits to the no-timeout mesh response
    ref = _search(c, "tm", {"query": {"match": {"body": "w1 w3"}},
                            "size": 6})
    assert resp["hits"] == ref["hits"]


def test_expired_deadline_hands_back_to_rpc_with_typed_reason(cluster):
    c = cluster
    node = c.nodes["node0"]
    ex = node.search_transport.mesh_executor
    scheduler = node.scheduler
    before = TELEMETRY.fallbacks.get("mesh_deadline_expired", 0)
    state = node._applied_state()
    targets = [{"index": "tm", "shard": s, "node": node.node_id,
                "copies": [node.node_id]} for s in range(3)]
    for t in targets:
        for sr in state.routing_table.index("tm").shard_group(t["shard"]):
            t["copies"] = [sr.node_id]
    out = []
    submitted = ex.try_submit(
        "tm", targets, {"query": {"match": {"body": "w1"}}, "size": 4},
        4, None, lambda results: out.append(results),
        deadline=scheduler.now() - 1.0)        # already expired
    assert submitted
    c.run_until(lambda: bool(out), 30.0)
    assert out[0] is None          # handed back to the RPC fan-out
    assert TELEMETRY.fallbacks["mesh_deadline_expired"] == before + 1
    assert ex.stats["mesh_fallbacks"] >= 1


# ---------------------------------------------------------------------------
# the node brings the device backend up when it starts
# ---------------------------------------------------------------------------

def test_started_node_serves_first_mesh_search_from_the_mesh():
    """Backend first-init belongs to ``Node.start``: a started node has
    live devices, and its FIRST mesh-eligible search runs as the mesh
    program — no per-shard RPC detour, no mesh fallback counted."""
    c = InProcessCluster(n_nodes=1, seed=61)
    c.start()
    try:
        node = c.nodes["node0"]
        assert node.local_node_stats()["device"]["devices"]
        client = c.client()
        _ok(*c.call(lambda cb: client.create_index("fm", {
            "settings": {"number_of_shards": 2, "number_of_replicas": 0},
            "mappings": {"properties": {"body": {"type": "text"}}}}, cb)))
        c.ensure_green("fm")
        for d in range(24):
            _ok(*c.call(lambda cb, d=d: client.index_doc(
                "fm", f"d{d}", {"body": f"w{d % 5} w{d % 7}"}, cb)))
        c.call(lambda cb: client.refresh("fm", cb))
        batcher = node.search_transport.batcher
        shard_queries = batcher.stats["queries_dispatched"]
        mesh_falls = {k: v for k, v in TELEMETRY.fallbacks.items()
                      if k.startswith("mesh_")}
        resp = _search(c, "fm", {"query": {"match": {"body": "w1"}},
                                 "size": 3})
        assert resp["_data_plane"] == "mesh_plane"
        assert resp["hits"]["hits"]
        assert batcher.stats["queries_dispatched"] == shard_queries
        assert {k: v for k, v in TELEMETRY.fallbacks.items()
                if k.startswith("mesh_")} == mesh_falls
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# _cat/indices: every index's status in ONE master request
# ---------------------------------------------------------------------------

def test_cat_indices_bulk_health_covers_every_index(cluster):
    from elasticsearch_tpu.rest.controller import RestRequest
    from elasticsearch_tpu.rest.routes import build_controller
    c = cluster
    controller = build_controller(c.client())
    out = []
    controller.dispatch(
        RestRequest(method="GET", path="/_cat/indices", query={},
                    body=None, raw_body=b""),
        lambda s, b: out.append((s, b)))
    c.run_until(lambda: bool(out), 30.0)
    status, body = out[0]
    assert status == 200
    text = str(body)
    for name in ("tm", "ts"):
        assert name in text
    assert "green" in text


def test_cluster_healths_async_bulk_and_fallback(cluster):
    c = cluster
    client = c.client()
    got = []
    client.cluster_healths_async(["tm", "ts", "absent-index"],
                                 lambda resp, err: got.append(resp))
    c.run_until(lambda: bool(got), 30.0)
    healths = got[0]["indices"]
    assert set(healths) == {"tm", "ts"}
    for h in healths.values():
        assert h["status"] in ("green", "yellow", "red")


# ---------------------------------------------------------------------------
# slow log carries the phase breakdown
# ---------------------------------------------------------------------------

def test_slow_log_line_carries_trace_summary(cluster, caplog):
    import logging
    c = cluster
    client = c.client()
    _ok(*c.call(lambda cb: client.update_settings(
        "ts", {"index.search.slowlog.threshold.query.warn": "0ms"}, cb)))
    try:
        with caplog.at_level(logging.INFO, logger="index.search.slowlog"):
            _search(c, "ts", {"query": {"match": {"body": "w1"}},
                              "size": 3})
        lines = [r.getMessage() for r in caplog.records
                 if r.name == "index.search.slowlog"]
        assert lines, "no slow-log line at a 0ms threshold"
        assert any("data_plane[" in ln and "phases[" in ln
                   for ln in lines), lines
    finally:
        _ok(*c.call(lambda cb: client.update_settings(
            "ts", {"index.search.slowlog.threshold.query.warn": None},
            cb)))


# ---------------------------------------------------------------------------
# unit: trace + histogram mechanics
# ---------------------------------------------------------------------------

def test_trace_span_clamps_and_dispatch_attribution():
    trace = SearchTrace("bm25", "solo")
    trace.add_span("queue_wait", 0)            # clamped: never reads absent
    with telemetry.activate(trace):
        with trace.span("device_dispatch"):
            telemetry.record_dispatch(3)
    trace.finish()
    assert trace.span_ns("queue_wait") == 1
    assert trace.dispatches == 3
    tree = trace.tree()
    dd = next(p for p in tree["phases"] if p["name"] == "device_dispatch")
    assert dd["dispatches"] == 3
    assert tree["time_in_nanos"] >= 1


def test_histogram_percentiles_and_lifetime_history():
    reg = telemetry.SearchTelemetry()
    for i in range(1000):
        t = SearchTrace("knn", "batch")
        t.add_span("device_dispatch", (i + 1) * 1000)
        t.total_ns = (i + 1) * 1000
        reg.observe(t)
    snap = reg.snapshot()["classes"]["knn|batch"]
    assert snap["queries"] == 1000
    lat = snap["latency"]
    assert lat["count"] == 1000
    # exponential buckets hold the WHOLE process history in fixed
    # memory: percentiles AND count are lifetime (the overload p99
    # contract — a flood of fast samples can't roll out a slow tail)
    assert lat["p50_ms"] > 0
    assert lat["p99_ms"] >= lat["p95_ms"] >= lat["p50_ms"]


@pytest.mark.slow
@pytest.mark.parametrize("seed",
                         [5 + 311 * k for k in range(max(CHAOS_SEEDS, 5))])
def test_profile_off_invisibility_seed_sweep(cluster, seed, tmp_path):
    """CI-widened sweep of the byte-invisibility golden (the tier-1 run
    covers CHAOS_SEEDS seeds; this covers >= 5)."""
    test_profile_off_byte_invisibility_all_planes(cluster, seed, tmp_path)


def test_classify_body_never_raises():
    assert telemetry.classify_body(None) == "other"
    assert telemetry.classify_body({"rank": {"rrf": {}}}) == "hybrid"
    assert telemetry.classify_body({"knn": {"field": "v"}}) == "knn"
    assert telemetry.classify_body(
        {"query": {"text_expansion": {}}}) == "sparse"
    assert telemetry.classify_body({"query": {"match": {}}}) == "bm25"
    assert telemetry.classify_body({"query": 7}) == "bm25"
    assert telemetry.classify_body({"rank": "junk"}) == "other"
