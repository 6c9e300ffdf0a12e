"""Device observatory: compile tracking, storms, attribution, surfaces.

The device-profile layer (search/device_profile.py) must route EVERY
jit entry point under ops/ and search/ (grep-guarded, the PR 8 "unknown
fallback reason pinned at zero" precedent), count compiles vs cache hits
per kernel family with live shape-bucket cardinality (the label built on
compiles only), detect recompile storms, attribute compiles to the
active request trace (``profile: true`` responses gain compile spans,
slow logs flag first-compile requests) — while profile-off responses
stay byte-identical whether the observatory records or not. Surfaces
under test:
``_nodes/stats`` "device_profile" (with the plane-HBM residency
timeline), the ``_cluster/stats`` fleet merge, and
``GET /_nodes/hot_spans``. The PR 10 follow-up fixes ride along: the C3
``clients`` term reads the data-node count from cluster state, and a
rejected tenant's Retry-After uses its fair-share drain rate.
"""

import copy
import json
import logging
import os
import re
import subprocess
import sys
import uuid

import numpy as np
import pytest

import jax.numpy as jnp

from elasticsearch_tpu.search import telemetry
from elasticsearch_tpu.search.device_profile import (
    DEVICE_PROFILE, ProfiledJit, merge_device_profile_sections,
    profiled_jit,
)
from elasticsearch_tpu.testing import InProcessCluster

CHAOS_SEEDS = int(os.environ.get("CHAOS_SEEDS", "1") or "1")

pytestmark = pytest.mark.observatory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ok(resp, err):
    assert err is None, f"unexpected error: {err}"
    return resp


def _fresh_family(prefix: str) -> str:
    """The registry is process-global: every test observes its own
    uniquely-named family so suites compose in any order."""
    return f"{prefix}_{uuid.uuid4().hex[:8]}"


# ---------------------------------------------------------------------------
# grep guard: every jit call site routes through the profiled wrapper
# ---------------------------------------------------------------------------

def test_no_raw_jit_call_sites_under_ops_and_search():
    """An uninstrumented kernel is invisible to the observatory — the
    zero-steady-state-recompiles gate and the per-family attribution
    both silently lose coverage. Pin raw jit call sites at ZERO under
    ops/, search/, the mesh kernel factory module, the legacy sharded
    search factories and the text-expansion model (the last two were
    outside the guard until their kernels joined the observatory); the
    one allowed speller is the wrapper itself."""
    raw_jit = re.compile(r"\bjax\s*\.\s*jit\b|\bfrom\s+jax\s+import\s+jit\b")
    pkg = os.path.join(REPO, "elasticsearch_tpu")
    targets = []
    for sub in ("ops", "search"):
        root = os.path.join(pkg, sub)
        for dirpath, _dirs, files in os.walk(root):
            targets.extend(os.path.join(dirpath, f)
                           for f in files if f.endswith(".py"))
    targets.append(os.path.join(pkg, "parallel", "mesh.py"))
    targets.append(os.path.join(pkg, "parallel", "sharded_search.py"))
    targets.append(os.path.join(pkg, "ml", "text_expansion.py"))
    offenders = []
    for path in targets:
        if path.endswith(os.path.join("search", "device_profile.py")):
            continue
        with open(path, encoding="utf-8") as fh:
            if raw_jit.search(fh.read()):
                offenders.append(os.path.relpath(path, pkg))
    assert not offenders, (
        f"raw jit call sites outside the profiled wrapper: {offenders} "
        f"— route them through search/device_profile.profiled_jit")


# ---------------------------------------------------------------------------
# compile vs cache-hit accounting
# ---------------------------------------------------------------------------

def test_compile_and_cache_hit_accounting():
    fam = _fresh_family("obs_add")

    @profiled_jit(fam, static_argnames=("k",))
    def kern(x, k: int):
        return x * 2.0 + k

    labels = []
    label_of = kern._label
    kern._label = lambda args, kwargs: labels.append(1) or \
        label_of(args, kwargs)
    kern(jnp.ones(8), k=3)             # compile #1
    kern(jnp.ones(8), k=3)             # cache hit
    kern(jnp.ones(8), k=3)             # cache hit
    kern(jnp.ones(16), k=3)            # new shape bucket: compile #2
    kern(jnp.ones(8), k=4)             # new static value: compile #3
    snap = DEVICE_PROFILE.snapshot()["families"][fam]
    assert snap["compiles"] == 3
    assert snap["cache_hits"] == 2
    assert snap["shape_buckets"] == 3
    assert snap["compile_ms_total"] >= snap["compile_ms_max"] > 0
    # the shape-bucket label is built for the compiles alone, and the
    # snapshot carries no per-call execute timing or cost estimate
    assert len(labels) == 3
    assert "execute_ewma_ms" not in snap and "cost" not in snap


def test_inlined_call_attributes_to_outer_family():
    """A profiled kernel traced INSIDE another profiled kernel must not
    count its tracer-call as a compile of its own family — the outer
    dispatch owns the device program."""
    inner_fam = _fresh_family("obs_inner")
    outer_fam = _fresh_family("obs_outer")

    @profiled_jit(inner_fam)
    def inner(x):
        return x + 1.0

    @profiled_jit(outer_fam)
    def outer(x):
        return inner(x) * 2.0

    outer(jnp.ones(4))
    fams = DEVICE_PROFILE.snapshot()["families"]
    assert fams[outer_fam]["compiles"] == 1
    assert inner_fam not in fams


# ---------------------------------------------------------------------------
# recompile-storm detector
# ---------------------------------------------------------------------------

def test_recompile_storm_detector_counts_and_logs(caplog):
    fam = _fresh_family("obs_storm")

    @profiled_jit(fam)
    def kern(x):
        return x + 1.0

    old = (DEVICE_PROFILE.storm_threshold, DEVICE_PROFILE.storm_window_s)
    DEVICE_PROFILE.configure(storm_threshold=3, storm_window_s=3600.0)
    try:
        with caplog.at_level(
                logging.WARNING,
                logger="elasticsearch_tpu.search.device_profile"):
            for n in range(1, 6):      # 5 distinct shapes = 5 compiles
                kern(jnp.ones(n))
        snap = DEVICE_PROFILE.snapshot()["families"][fam]
        assert snap["compiles"] == 5
        assert snap["recompile_storms"] >= 1
        assert any("RECOMPILE STORM" in r.getMessage()
                   for r in caplog.records)
    finally:
        DEVICE_PROFILE.configure(storm_threshold=old[0],
                                 storm_window_s=old[1])


# ---------------------------------------------------------------------------
# request attribution: compile spans + the slow-log first-compile flag
# ---------------------------------------------------------------------------

def test_compile_attributes_to_active_trace():
    fam = _fresh_family("obs_trace")

    @profiled_jit(fam)
    def kern(x):
        return x * 3.0

    first = telemetry.SearchTrace("bm25", "solo")
    with telemetry.activate(first):
        kern(jnp.ones(8))
    assert first.compiles == 1
    compile_spans = [(n, m) for n, _d, m in first.spans if n == "compile"]
    assert compile_spans and compile_spans[0][1]["family"] == fam
    assert "compile_ms" in compile_spans[0][1]
    # the slow-log line flags the first-compile request…
    assert f"compiles[1]" in first.summary()
    # …and the profile tree carries the span
    assert any(p["name"] == "compile"
               for p in first.tree()["phases"])

    second = telemetry.SearchTrace("bm25", "solo")
    with telemetry.activate(second):
        kern(jnp.ones(8))              # cache hit: no attribution
    assert second.compiles == 0
    assert "compiles[" not in second.summary()
    assert not any(n == "compile" for n, _d, _m in second.spans)


# ---------------------------------------------------------------------------
# serving-path invisibility + surfaces (cluster-backed)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    """One node, two indices: "om" (3 shards — the mesh-eligible
    fan-out) and "os1" (1 shard, 2 segments — batch/plane/solo)."""
    c = InProcessCluster(n_nodes=1, seed=61)
    c.start()
    client = c.client()
    rng = np.random.default_rng(61)
    vocab = [f"w{i}" for i in range(24)]
    for name, shards in (("om", 3), ("os1", 1)):
        _ok(*c.call(lambda cb, n=name, s=shards: client.create_index(
            n, {"settings": {"number_of_shards": s,
                             "number_of_replicas": 0},
                "mappings": {"properties": {
                    "body": {"type": "text"},
                    "vec": {"type": "dense_vector", "dims": 8,
                            "similarity": "cosine"},
                    "feats": {"type": "rank_features"}}}}, cb)))
        c.ensure_green(name)
        for d in range(60):
            _ok(*c.call(lambda cb, n=name, d=d: client.index_doc(
                n, f"d{d}", {
                    "body": " ".join(rng.choice(
                        vocab, size=int(rng.integers(4, 10)))),
                    "vec": [float(x) for x in rng.standard_normal(8)],
                    "feats": {f"f{j}": float(rng.random() + 0.1)
                              for j in rng.integers(0, 10, 3)}}, cb)))
            if d == 30:
                c.call(lambda cb, n=name: client.refresh(n, cb))
        c.call(lambda cb, n=name: client.refresh(n, cb))
    # backend first-init outside any measured wave
    c.call(lambda cb: client.search(
        "om", {"query": {"match": {"body": "w0"}}, "size": 1}, cb))
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


@pytest.mark.parametrize("seed", [7 + 419 * k for k in range(CHAOS_SEEDS)])
def test_profile_off_byte_invisibility_with_observatory(cluster, seed):
    """Profile-off responses must be byte-identical whether the device
    observatory records or not, on the fan-out AND single-shard paths —
    compile tracking is pure observation (task status / stats / logs
    only), never a response mutation."""
    c = cluster
    rng = np.random.default_rng(seed)
    bodies = _bodies(rng)
    for index in ("om", "os1"):
        recording = _wave(c, index, bodies)
        assert DEVICE_PROFILE.enabled
        DEVICE_PROFILE.enabled = False
        try:
            silent = _wave(c, index, bodies)
        finally:
            DEVICE_PROFILE.enabled = True
        for body, a, b in zip(bodies, recording, silent):
            raw = json.dumps(a, sort_keys=True)
            for key in ('"compile"', '"compile_ms"', '"device_profile"',
                        '"shape_buckets"'):
                assert key not in raw, (index, body, key)
            sa = {k: v for k, v in a.items() if k != "took"}
            sb = {k: v for k, v in b.items() if k != "took"}
            assert json.dumps(sa, sort_keys=True) == \
                json.dumps(sb, sort_keys=True), (index, body)


def test_device_profile_stats_section_and_no_unknown_families(cluster):
    c = cluster
    rng = np.random.default_rng(17)
    _wave(c, "os1", _bodies(rng))
    node = c.nodes["node0"]
    narrow = node.local_node_stats(sections=["device_profile"])
    section = narrow["device_profile"]
    assert section["families"], "no kernel families recorded"
    # zero "unknown" kernel-family attribution: every family is a named
    # kernel, every recorded call is attributed to one
    for name, fam in section["families"].items():
        assert name and name != "unknown"
        assert fam["compiles"] + fam["cache_hits"] > 0
    # serving kernels are present by their real names
    assert any(name.startswith(("bm25", "knn", "sparse"))
               for name in section["families"])
    assert section["total_cache_hits"] > 0
    # the residency timeline rides the same section
    for key in ("plane_residency", "mesh_plane_residency"):
        res = section[key]
        assert set(res) >= {"resident_bytes_total", "high_water_bytes",
                            "planes", "evictions_by_cause",
                            "generations_built"}
    # section narrowing: only the asked-for section is built
    assert set(narrow) == {"name", "device_profile"}


def test_cluster_stats_serves_merged_device_profile(cluster):
    from elasticsearch_tpu.rest.controller import RestRequest
    from elasticsearch_tpu.rest.routes import build_controller
    c = cluster
    rng = np.random.default_rng(19)
    _wave(c, "os1", _bodies(rng))
    rc = build_controller(c.client())
    box = []
    rc.dispatch(RestRequest(method="GET", path="/_cluster/stats"),
                lambda status, body: box.append((status, body)))
    c.run_until(lambda: bool(box), 120.0)
    status, body = box[0]
    assert status == 200
    merged = body["device_profile"]
    assert merged["families"] and merged["total_compiles"] > 0
    entry = next(iter(merged["families"].values()))
    for field in ("compiles", "cache_hits", "compile_ms_total",
                  "compile_ms_max", "shape_buckets", "recompile_storms"):
        assert field in entry


def test_merge_device_profile_sections_sums_and_maxes():
    a = {"families": {"bm25_flat": {
            "compiles": 2, "cache_hits": 10, "compile_ms_total": 30.0,
            "compile_ms_max": 20.0, "shape_buckets": 2,
            "recompile_storms": 0}},
         "total_compiles": 2, "total_cache_hits": 10,
         "recompile_storms": 0}
    b = {"families": {"bm25_flat": {
            "compiles": 3, "cache_hits": 5, "compile_ms_total": 45.0,
            "compile_ms_max": 40.0, "shape_buckets": 3,
            "recompile_storms": 1}},
         "total_compiles": 3, "total_cache_hits": 5,
         "recompile_storms": 1}
    merged = merge_device_profile_sections([a, b, {}])
    fam = merged["families"]["bm25_flat"]
    assert fam["compiles"] == 5 and fam["cache_hits"] == 15
    assert fam["compile_ms_total"] == 75.0
    assert fam["compile_ms_max"] == 40.0     # max, never a sum
    assert fam["shape_buckets"] == 5
    assert merged["total_compiles"] == 5
    assert merged["recompile_storms"] == 1


# ---------------------------------------------------------------------------
# hot spans: the hot-threads analog over the data planes
# ---------------------------------------------------------------------------

def test_hot_spans_reports_in_flight_search_tasks(cluster):
    from elasticsearch_tpu import monitor
    c = cluster
    node = c.nodes["node0"]
    tm = node.task_manager
    older = tm.register("indices:data/read/search[phase/query]",
                        "shard query [om][0]", cancellable=True)
    older.start_time_ms -= 250.0       # ran longer than the newer one
    older.status = {"phase": "dispatch", "data_plane": "batch",
                    "occupancy": 4}
    newer = tm.register("indices:data/read/search",
                        "coordinated search [om]")
    newer.status = {"phase": "query", "data_plane": "mesh_plane"}
    unrelated = tm.register("indices:data/write/bulk", "bulk")
    try:
        report = monitor.hot_spans_report(node, limit=8)
        assert report["in_flight_total"] == 2     # bulk excluded
        spans = report["spans"]
        assert [s["task"] for s in spans] == \
            [older.task_id, newer.task_id]        # longest first
        assert spans[0]["phase"] == "dispatch"
        assert spans[0]["data_plane"] == "batch"
        assert spans[0]["occupancy"] == 4
        assert spans[0]["elapsed_ms"] >= spans[1]["elapsed_ms"]
        assert "queued_members" in report
        assert "node_pressure" in report
    finally:
        for t in (older, newer, unrelated):
            tm.unregister(t)


def test_hot_spans_rest_route(cluster):
    from elasticsearch_tpu.rest.controller import RestRequest
    from elasticsearch_tpu.rest.routes import build_controller
    c = cluster
    node = c.nodes["node0"]
    task = node.task_manager.register(
        "indices:data/read/search[phase/query]", "shard query [om][1]")
    task.status = {"phase": "queued", "data_plane": "batch"}
    try:
        rc = build_controller(c.client())
        box = []
        rc.dispatch(RestRequest(method="GET", path="/_nodes/hot_spans",
                                query={"size": "4"}),
                    lambda status, body: box.append((status, body)))
        c.run_until(lambda: bool(box), 60.0)
        status, body = box[0]
        assert status == 200
        report = body[node.node_id]
        assert report["in_flight_total"] >= 1
        assert any(s["task"] == task.task_id for s in report["spans"])
    finally:
        node.task_manager.unregister(task)


# ---------------------------------------------------------------------------
# plane-HBM residency timeline
# ---------------------------------------------------------------------------

def test_plane_residency_timeline_and_eviction_causes():
    from elasticsearch_tpu.index import InternalEngine
    from elasticsearch_tpu.mapping import MapperService
    from elasticsearch_tpu.ops.device_segment import PLANES
    eng = InternalEngine(
        MapperService({"properties": {"body": {"type": "text"}}}),
        shard_label="obs_res")
    rng = np.random.default_rng(23)
    for i in range(40):
        eng.index(str(i), {"body": " ".join(
            f"w{int(x)}" for x in rng.integers(0, 8, 6))})
        if i == 20:
            eng.refresh()
    eng.refresh()
    old_min = PLANES.min_segments
    PLANES.min_segments = 1
    gen_before = PLANES._gen
    try:
        reader = eng.acquire_reader()
        part = PLANES.get(list(reader.segments), "postings", "body")
        assert part is not None
        res = PLANES.residency_snapshot()
        assert res["resident_bytes_total"] > 0
        assert res["high_water_bytes"] >= res["resident_bytes_total"]
        assert res["generations_built"] > gen_before
        entry = next(e for e in res["planes"]
                     if e["kind"] == "postings" and e["field"] == "body")
        assert entry["bytes"] > 0 and entry["age_s"] >= 0.0
        # eviction causes are typed: a breaker-pressure shed names itself
        before = PLANES.evictions_by_cause.get("breaker_pressure", 0)
        dropped = PLANES.evict_cold()   # every resident plane sheds
        assert dropped >= 1
        assert PLANES.evictions_by_cause["breaker_pressure"] == \
            before + dropped
        assert PLANES.residency_snapshot()["resident_bytes_total"] == 0
    finally:
        PLANES.min_segments = old_min
        PLANES.clear()


# ---------------------------------------------------------------------------
# PR 10 follow-ups riding along
# ---------------------------------------------------------------------------

def test_c3_clients_term_uses_data_node_count():
    """The reference's C3 `clients` is the DATA-NODE count from cluster
    state; the coordinator's tracked-node map undercounts until every
    node has answered once."""
    from elasticsearch_tpu.action.response_collector import (
        ResponseCollectorService,
    )
    svc = ResponseCollectorService()
    svc.on_send("n1")
    svc.on_response("n1", 0.010, service_ms=5.0, queue_depth=2.0)
    svc.on_send("n1")                 # one outstanding
    rank_tracked = svc.rank("n1")     # clients = tracked nodes = 1
    svc.set_data_node_count(5)
    rank_state = svc.rank("n1")       # clients = data nodes = 5
    # with outstanding > 0 a larger clients term inflates q_hat, so the
    # state-fed rank must penalize concurrency harder
    assert rank_state > rank_tracked
    # the exact formula: r - s + (1 + outstanding*clients + q)^3 * s
    stats = svc._nodes["n1"]
    s = stats.service_ewma_ms
    expected = stats.ewma_ms - s + \
        (1.0 + stats.outstanding * 5 + stats.queue_ewma) ** 3 * s
    assert rank_state == pytest.approx(expected)
    # an unset count (no state yet) falls back to the tracked map
    svc.set_data_node_count(0)
    assert svc.rank("n1") == pytest.approx(rank_tracked)


def test_retry_after_uses_tenant_fair_share_rate():
    from elasticsearch_tpu.utils.threadpool import Pool
    clock = {"t": 0.0}
    pool = Pool("search", 1, 100, now_fn=lambda: clock["t"])
    pool.frame_size = 10
    # measure a 10/s completion rate
    for _ in range(10):
        pool.submit(lambda: None)
        clock["t"] += 0.1
        pool.release()
    assert pool.task_rate == pytest.approx(10.0)
    # occupy the single slot so submissions queue per tenant
    pool.submit(lambda: None)
    for _ in range(6):
        pool.submit(lambda: None, tenant="hot",
                    on_reject=lambda e: None)
    for _ in range(2):
        pool.submit(lambda: None, tenant="bg",
                    on_reject=lambda e: None)
    # two tenants drain round-robin: "hot" (6 deep) drains at HALF the
    # pool rate -> ceil((6+1) * 2 / 10) = 2s, not ceil((8+1)/10) = 1s
    assert pool.retry_after_s("hot") == 2
    assert pool.retry_after_s("bg") == 1
    # the no-tenant (and single-tenant) forms keep the whole-pool
    # estimate — existing callers and tests unchanged
    assert pool.retry_after_s() == 1


# ---------------------------------------------------------------------------
# the bench gate (slow: spawns a subprocess bench run)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_device_profile_gate_passes():
    """CI smoke: ``bench.py --device-profile`` runs the steady-state
    loop for bm25/knn/sparse and exits 0 only when ZERO steady-state
    recompiles were observed — the regression gate that keeps the pow2
    bucketing invariants honest."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--device-profile"],
        capture_output=True, text=True, timeout=600, env=env)
    line = next((ln for ln in reversed(p.stdout.splitlines())
                 if ln.startswith("{")), None)
    assert line, f"no JSON line (rc={p.returncode}): {p.stderr[-400:]}"
    out = json.loads(line)["configs"]["device_profile"]
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-300:])
    assert out["zero_steady_state_recompiles"] is True
    for cls in ("bm25", "knn", "sparse"):
        assert out[cls]["steady_recompiles"] == 0
        assert out[cls]["warmup_compiles"] >= 1
