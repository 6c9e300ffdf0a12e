"""Quantized coarse tier for every scatter-bound class: golden parity,
adaptive depth, mesh-served mirrors, degradation.

The two-tier pattern (bf16/int8 coarse pass over the full plane + exact
f32 re-rank of the top k' candidates, adaptive depth driven by the
coarse margin at position k') must be INVISIBLE in results: hits,
scores, totals and relations identical to the exact path for bm25,
sparse and kNN — across deletes, filters, every totals mode and
refresh-during-query — with escalation deterministic, the mesh-served
quantized mirrors identical to the per-shard fan-out, and a
breaker-starved node serving exact with identical results.
"""

import os
import types

import numpy as np
import pytest

from elasticsearch_tpu.index import InternalEngine
from elasticsearch_tpu.indices.breaker import BREAKERS
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.ops.device_segment import MESH_PLANES, PLANES
from elasticsearch_tpu.search import dsl, telemetry
from elasticsearch_tpu.search.phase import parse_sort, query_shard
from elasticsearch_tpu.search.telemetry import TELEMETRY

CHAOS_SEEDS = int(os.environ.get("CHAOS_SEEDS", "1") or "1")

pytestmark = pytest.mark.quantized

# a corpus this size with depth 32 clears the 4x engage threshold for
# every class, so the coarse tier actually serves in these tests
N_DOCS = 1100
DEPTH = 32


@pytest.fixture(autouse=True)
def _tier_defaults():
    PLANES.clear()
    MESH_PLANES.clear()
    PLANES.enabled = True
    PLANES.min_segments = 2
    PLANES.rerank_depth = DEPTH
    PLANES.rerank_depth_max = 1024
    PLANES.quantized = True
    PLANES.max_bytes = 0
    yield
    PLANES.clear()
    MESH_PLANES.clear()
    PLANES.enabled = True
    PLANES.quantized = True
    PLANES.rerank_depth = 128
    PLANES.rerank_depth_max = 1024
    PLANES.max_bytes = 0
    MESH_PLANES.max_devices = 0


def _engine(seed: int, n_docs: int = N_DOCS, label: str = "qt",
            vec_options: dict = None):
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(60)]
    eng = InternalEngine(
        MapperService({"properties": {
            "body": {"type": "text"},
            "vec": {"type": "dense_vector", "dims": 8,
                    "similarity": "cosine",
                    **({"index_options": vec_options}
                       if vec_options else {})},
            "feats": {"type": "rank_features"},
            "tag": {"type": "keyword"}}}),
        shard_label=f"{label}{seed}")
    for i in range(n_docs):
        eng.index(str(i), {
            "body": " ".join(rng.choice(
                vocab, size=int(rng.integers(4, 16)))),
            "vec": [float(x) for x in rng.standard_normal(8)],
            "feats": {f"f{j}": float(rng.random() + 0.1)
                      for j in rng.integers(0, 15, 3)},
            "tag": f"t{i % 3}"})
        if i in (n_docs // 3, 2 * n_docs // 3):
            eng.refresh()
    eng.refresh()
    return eng, rng


def _bodies(rng):
    qv = [float(x) for x in rng.standard_normal(8)]
    return [
        {"match": {"body": "w1 w3 w7"}},
        {"knn": {"field": "vec", "k": 7, "query_vector": qv}},
        {"knn": {"field": "vec", "k": 7, "query_vector": qv,
                 "filter": {"term": {"tag": "t1"}}}},
        {"text_expansion": {"feats": {"tokens": {
            "f1": 1.2, "f4": 0.7, "f9": 0.4}}}},
    ]


def _run(eng, reader, body, track=10_000, size=10):
    return query_shard(reader, eng.mappers, dsl.parse_query(body),
                       size=size, sort=parse_sort(None),
                       track_total_hits=track)


def _assert_same(r_a, r_b):
    assert [(d.segment_idx, d.doc) for d in r_a.docs] == \
        [(d.segment_idx, d.doc) for d in r_b.docs]
    np.testing.assert_allclose([d.score for d in r_a.docs],
                               [d.score for d in r_b.docs],
                               rtol=1e-6, atol=1e-7)
    assert r_a.total_hits == r_b.total_hits
    assert r_a.total_relation == r_b.total_relation


# ---------------------------------------------------------------------------
# golden parity: quantized vs exact, all coarse-tier classes, all modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [71 + 1000 * k for k in range(CHAOS_SEEDS)])
@pytest.mark.parametrize("track", [10_000, 5, False])
def test_golden_quantized_vs_exact_all_classes(seed, track):
    """bm25 / filtered+plain kNN / sparse: the coarse tier's results are
    identical to the exact plane path in every totals mode — tracked,
    clipped, disabled."""
    eng, rng = _engine(seed)
    reader = eng.acquire_reader()
    for body in _bodies(rng):
        PLANES.quantized = False
        exact = _run(eng, reader, body, track=track)
        PLANES.quantized = True
        quant = _run(eng, reader, body, track=track)
        _assert_same(exact, quant)
    # the text and sparse tiers actually engaged (kNN engagement is
    # covered by the plane suite)
    assert PLANES.stats["quantized_queries"] >= 2
    snap = PLANES.stats_snapshot()
    assert snap["rerank_depth_histogram"], "histogram must record depths"


@pytest.mark.parametrize("seed", [79 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_golden_quantized_with_deletes(seed):
    """Deleted docs stay out of coarse-tier results (live masks ride the
    reader snapshot into both tiers) and never resurface via the
    candidate plane."""
    eng, rng = _engine(seed)
    deleted = {str(i) for i in range(0, N_DOCS, 7)}
    for i in range(0, N_DOCS, 7):
        eng.delete(str(i))
    eng.refresh()
    reader = eng.acquire_reader()
    for body in _bodies(rng):
        PLANES.quantized = False
        exact = _run(eng, reader, body)
        PLANES.quantized = True
        quant = _run(eng, reader, body)
        _assert_same(exact, quant)
        for d in quant.docs:
            assert reader.segments[d.segment_idx].ids[d.doc] not in deleted


@pytest.mark.parametrize("seed", [83 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_refresh_during_query_quantized_parity(seed):
    """A point-in-time reader acquired before a refresh keeps serving
    the OLD segment set through the coarse tier (mirrors are keyed by
    plane generation), identical to exact."""
    eng, rng = _engine(seed)
    reader = eng.acquire_reader()       # PIT snapshot
    for i in range(N_DOCS, N_DOCS + 60):
        eng.index(str(i), {"body": "w1 w3",
                           "vec": [float(x)
                                   for x in rng.standard_normal(8)],
                           "feats": {"f1": 1.0}, "tag": "t0"})
    eng.refresh()
    body = {"match": {"body": "w1 w3 w7"}}
    PLANES.quantized = False
    exact = _run(eng, reader, body)
    PLANES.quantized = True
    quant = _run(eng, reader, body)
    _assert_same(exact, quant)
    # and the NEW reader sees the appended docs through the tier too
    reader2 = eng.acquire_reader()
    PLANES.quantized = False
    exact2 = _run(eng, reader2, body)
    PLANES.quantized = True
    quant2 = _run(eng, reader2, body)
    _assert_same(exact2, quant2)
    assert exact2.total_hits > exact.total_hits


# ---------------------------------------------------------------------------
# adaptive depth: escalation is deterministic and parity-preserving
# ---------------------------------------------------------------------------

def test_adaptive_escalation_deterministic_on_tied_scores():
    """A corpus where MANY docs share identical text produces massive
    exact-score ties at the coarse cut: the margin cannot prove parity
    at the starting depth, so the tier must escalate (and possibly serve
    exact) — twice in a row, with identical results both times, and
    results identical to the exact path."""
    eng = InternalEngine(
        MapperService({"properties": {"body": {"type": "text"}}}),
        shard_label="qt_tied")
    for i in range(900):
        # only 4 distinct documents: scores tie in huge groups
        eng.index(str(i), {"body": ["w1 w2", "w1 w3", "w2 w3",
                                    "w1 w2 w3"][i % 4]})
        if i in (300, 600):
            eng.refresh()
    eng.refresh()
    reader = eng.acquire_reader()
    body = {"match": {"body": "w1 w2"}}
    PLANES.quantized = False
    exact = _run(eng, reader, body)
    PLANES.quantized = True
    esc0 = PLANES.stats["rerank_escalations"]
    fb0 = PLANES.stats["quantized_exact_fallbacks"]
    q1 = _run(eng, reader, body)
    q2 = _run(eng, reader, body)
    _assert_same(exact, q1)
    _assert_same(q1, q2)
    # the margin had to do SOMETHING about the ties — deepen, or give
    # up and serve exact — and it did the same thing both times
    moved = (PLANES.stats["rerank_escalations"] - esc0) \
        + (PLANES.stats["quantized_exact_fallbacks"] - fb0)
    assert moved >= 2 and moved % 2 == 0


def test_depth_cap_serves_exact_with_typed_fallback():
    """rerank_depth_max == rerank_depth: an escalation-needing query
    cannot deepen, so the EXACT path serves (identical results) and the
    typed plane_quantized_fallback reason is counted."""
    eng = InternalEngine(
        MapperService({"properties": {"body": {"type": "text"}}}),
        shard_label="qt_cap")
    for i in range(900):
        eng.index(str(i), {"body": "w1 w2" if i % 2 else "w1 w3"})
        if i == 450:
            eng.refresh()
    eng.refresh()
    reader = eng.acquire_reader()
    body = {"match": {"body": "w1 w2"}}
    PLANES.rerank_depth_max = DEPTH     # no room to deepen
    PLANES.quantized = False
    exact = _run(eng, reader, body)
    before = TELEMETRY.snapshot()["fallback_reasons"].get(
        telemetry.PLANE_QUANTIZED_FALLBACK, 0)
    fb0 = PLANES.stats["quantized_exact_fallbacks"]
    PLANES.quantized = True
    quant = _run(eng, reader, body)
    _assert_same(exact, quant)
    assert PLANES.stats["quantized_exact_fallbacks"] > fb0
    after = TELEMETRY.snapshot()["fallback_reasons"].get(
        telemetry.PLANE_QUANTIZED_FALLBACK, 0)
    assert after > before


# ---------------------------------------------------------------------------
# breaker-starved degradation: exact serves, identical results
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [89 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_breaker_starved_mirror_serves_exact_identical(seed):
    """With the plane resident but the device breaker exhausted, the
    quantized mirror upload is REFUSED: the exact path serves with
    identical results, the refusal is memoized (no per-query
    re-quantization), and the typed fallback is counted."""
    eng, rng = _engine(seed)
    reader = eng.acquire_reader()
    body = {"match": {"body": "w1 w3 w7"}}
    PLANES.quantized = False
    exact = _run(eng, reader, body)     # plane builds here
    breaker = BREAKERS.breaker("device")
    old_limit = breaker.limit
    try:
        breaker.limit = breaker.used + 1    # no headroom for mirrors
        PLANES.quantized = True
        q0 = PLANES.stats["quantized_queries"]
        quant = _run(eng, reader, body)
        _assert_same(exact, quant)
        assert PLANES.stats["quantized_queries"] == q0
        assert PLANES.stats["quantized_exact_fallbacks"] >= 1
        # memoized refusal: a second query must not pay quantization
        fb1 = PLANES.stats["quantized_exact_fallbacks"]
        quant2 = _run(eng, reader, body)
        _assert_same(exact, quant2)
        assert PLANES.stats["quantized_exact_fallbacks"] >= fb1
    finally:
        breaker.limit = old_limit


# ---------------------------------------------------------------------------
# mesh-served quantized mirrors: identical to the per-shard fan-out
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [97 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_mesh_quantized_identity_vs_per_shard_fanout(seed):
    """Two co-located engage-sized shards: the mesh-served quantized
    tier returns candidate-for-candidate identical results (docs AND
    scores AND totals) to the per-shard plane fan-out for bm25 (every
    totals mode), kNN and sparse — the single-device byte-identity
    contract extended to the quantized tier."""
    from elasticsearch_tpu.search.batch_executor import (
        BatchSpec, _build_ctxs,
    )
    from elasticsearch_tpu.search.phase import shard_term_stats
    from elasticsearch_tpu.search.plane_exec import (
        mesh_knn_winners, mesh_sparse_topk, mesh_wand_topk,
        plane_knn_winners, plane_sparse_topk, plane_wand_topk,
    )
    rng = np.random.default_rng(seed)
    engines = [_engine(seed + s, n_docs=900, label="qtm")[0]
               for s in range(2)]
    mappers = engines[0].mappers
    readers = [e.acquire_reader() for e in engines]
    shard_segments = [(("ix", s), list(r.segments))
                      for s, r in enumerate(readers)]
    PLANES.min_segments = 1
    MESH_PLANES.enabled = True
    MESH_PLANES.min_shards = 1
    MESH_PLANES.max_devices = 1     # the byte-identity baseline layout

    clause_lists = [[("w1 w3 w7", 1.0)], [("w2 w5", 1.0)]]
    shard_ctxs = []
    for r in readers:
        doc_count = sum(seg.n_docs for seg in r.segments)
        dfs = {}
        for cl in clause_lists:
            _dc, m_dfs = shard_term_stats(
                r, mappers, dsl.Match(field="body", text=cl[0][0]))
            for fname, termmap in m_dfs.items():
                dfs.setdefault(fname, {}).update(termmap)
        shard_ctxs.append(_build_ctxs(r, mappers, doc_count, dfs))

    q0 = MESH_PLANES.stats["mesh_quantized_queries"]
    for track in (10_000, 5, 0):
        mp = MESH_PLANES.get(shard_segments, "postings", "body")
        assert mp is not None
        mesh = mesh_wand_topk(shard_ctxs, mp, "body", clause_lists, 10,
                              track)
        parts = [PLANES.get(list(r.segments), "postings", "body")
                 for r in readers]
        fan = [plane_wand_topk(shard_ctxs[s], parts[s], "body",
                               clause_lists, 10, track)
               for s in range(2)]
        for s in range(2):
            for q in range(len(clause_lists)):
                assert [(c.segment_idx, c.doc, c.score)
                        for c in mesh[s][q][0]] == \
                    [(c.segment_idx, c.doc, c.score)
                     for c in fan[s][q][0]]
                assert mesh[s][q][1:3] == fan[s][q][1:3]
    assert MESH_PLANES.stats["mesh_quantized_queries"] > q0

    specs = [BatchSpec(kind="knn", field="vec", window=10,
                       clip_limit=None, k=10, num_candidates=50,
                       boost=1.0,
                       query_vector=[float(x)
                                     for x in rng.standard_normal(8)])
             for _ in range(2)]
    mv = MESH_PLANES.get(shard_segments, "vectors", "vec")
    mesh_k = mesh_knn_winners(shard_ctxs, mv, "vec", specs, 10)
    vparts = [PLANES.get(list(r.segments), "vectors", "vec")
              for r in readers]
    fan_k = [plane_knn_winners(shard_ctxs[s], vparts[s], "vec", specs,
                               10) for s in range(2)]
    assert all(mesh_k[s][q] == fan_k[s][q]
               for s in range(2) for q in range(2))

    expansions = [[("f1", 1.2), ("f4", 0.7)], [("f2", 0.9), ("f9", 0.4)]]
    fp = MESH_PLANES.get(shard_segments, "features", "feats")
    mesh_s = mesh_sparse_topk(shard_ctxs, fp, "feats", expansions, 10)
    fparts = [PLANES.get(list(r.segments), "features", "feats")
              for r in readers]
    fan_s = [plane_sparse_topk(shard_ctxs[s], fparts[s], "feats",
                               expansions, 10) for s in range(2)]
    for s in range(2):
        for q in range(2):
            assert [(c.segment_idx, c.doc, c.score)
                    for c in mesh_s[s][q][0]] == \
                [(c.segment_idx, c.doc, c.score) for c in fan_s[s][q][0]]
            assert mesh_s[s][q][1] == fan_s[s][q][1]
    assert MESH_PLANES.stats["mesh_quantized_mirror_builds"] >= 3


@pytest.mark.parametrize("seed", [113 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_flat_mapped_knn_stays_exact_solo_and_mesh(seed, monkeypatch):
    """``index_options: {type: flat}`` asks for exact answers: the
    batched classification keeps it on the batch plane, and the plane
    and the mesh serve it with the exact kernel — zero quantized
    queries, ANN-sized segments included — returning the per-segment
    exact path's hits, while the same vectors auto-mapped DO engage the
    int8 tier (the control)."""
    from elasticsearch_tpu.search import execute as execute_mod
    from elasticsearch_tpu.search.batch_executor import (
        BatchSpec, _build_ctxs, _classify,
    )
    from elasticsearch_tpu.search.plane_exec import (
        mesh_knn_winners, plane_knn_winners,
    )
    engines = [_engine(seed + s, n_docs=900, label=f"qtf{s}",
                       vec_options={"type": "flat"})[0] for s in range(2)]
    auto, rng = _engine(seed, n_docs=900, label="qtfa")
    readers = [e.acquire_reader() for e in engines]
    qv = [float(x) for x in rng.standard_normal(8)]
    body = {"knn": {"field": "vec", "k": 7, "query_vector": qv}}
    assert _classify({"body": {"query": body}, "window": 10},
                     engines[0].mappers).kind == "knn"

    PLANES.enabled = False                  # the per-segment exact path
    ref = [_run(e, r, body) for e, r in zip(engines, readers)]
    PLANES.enabled = True
    q0 = PLANES.stats["quantized_queries"]
    b0 = PLANES.stats["plane_builds"]
    for e, r, want in zip(engines, readers, ref):
        _assert_same(want, _run(e, r, body))
    assert PLANES.stats["plane_builds"] >= b0 + 2   # the plane served
    assert PLANES.stats["quantized_queries"] == q0
    _run(auto, auto.acquire_reader(), body)
    assert PLANES.stats["quantized_queries"] > q0, "control: no engage"

    # every segment is now ANN-sized: an auto field would leave the mesh
    # for the per-segment IVF route; a flat one stays exact on the mesh
    monkeypatch.setattr(execute_mod, "ANN_DEFAULT_MIN_DOCS", 64)
    PLANES.min_segments = 1
    MESH_PLANES.enabled = True
    MESH_PLANES.min_shards = 1
    MESH_PLANES.max_devices = 1
    shard_segments = [(("ix", s), list(r.segments))
                      for s, r in enumerate(readers)]
    shard_ctxs = [_build_ctxs(r, engines[0].mappers,
                              sum(g.n_docs for g in r.segments), None)
                  for r in readers]
    specs = [BatchSpec(kind="knn", field="vec", window=10,
                       clip_limit=None, k=7, num_candidates=50,
                       boost=1.0, query_vector=qv)]
    mq0 = MESH_PLANES.stats["mesh_quantized_queries"]
    mv = MESH_PLANES.get(shard_segments, "vectors", "vec")
    mesh_k = mesh_knn_winners(shard_ctxs, mv, "vec", specs, 7)
    assert MESH_PLANES.stats["mesh_quantized_queries"] == mq0
    for s, r in enumerate(readers):
        part = PLANES.get(list(r.segments), "vectors", "vec")
        assert mesh_k[s][0] == plane_knn_winners(shard_ctxs[s], part,
                                                 "vec", specs, 7)[0]
        assert [(a, b) for a, b, _ in mesh_k[s][0]] == \
            [(d.segment_idx, d.doc) for d in ref[s].docs]
    assert PLANES.stats["quantized_queries"] == q0 + 1


def test_mesh_mixed_knn_engagement_raises_mesh_fallback():
    """One engage-sized shard + one tiny shard: mesh kNN must hand the
    fan-out back to the per-shard path (typed mesh_quantized_fallback
    reason on the MeshFallback) — only the RPC fan-out can serve each
    shard its own tier byte-identically."""
    from elasticsearch_tpu.search.batch_executor import (
        BatchSpec, _build_ctxs,
    )
    from elasticsearch_tpu.search.plane_exec import (
        MeshFallback, mesh_knn_winners,
    )
    rng = np.random.default_rng(3)
    big, _ = _engine(301, n_docs=900, label="qtx")
    small, _ = _engine(302, n_docs=90, label="qty")
    readers = [big.acquire_reader(), small.acquire_reader()]
    shard_segments = [(("ix", s), list(r.segments))
                      for s, r in enumerate(readers)]
    PLANES.min_segments = 1
    MESH_PLANES.enabled = True
    MESH_PLANES.min_shards = 1
    MESH_PLANES.max_devices = 1
    mv = MESH_PLANES.get(shard_segments, "vectors", "vec")
    assert mv is not None
    shard_ctxs = [_build_ctxs(r, big.mappers,
                              sum(s.n_docs for s in r.segments), None)
                  for r in readers]
    specs = [BatchSpec(kind="knn", field="vec", window=10,
                       clip_limit=None, k=10, num_candidates=50,
                       boost=1.0,
                       query_vector=[float(x)
                                     for x in rng.standard_normal(8)])]
    with pytest.raises(MeshFallback) as ei:
        mesh_knn_winners(shard_ctxs, mv, "vec", specs, 10)
    assert ei.value.reason == telemetry.MESH_QUANTIZED_FALLBACK


# ---------------------------------------------------------------------------
# dynamic settings: storm thresholds + rerank depth applied from state
# ---------------------------------------------------------------------------

def _fake_state(version: int, settings: dict):
    return types.SimpleNamespace(
        version=version,
        metadata=types.SimpleNamespace(persistent_settings=settings))


def test_device_profile_storm_settings_from_state():
    """search.device_profile.storm_* are dynamic cluster settings now:
    configure_from_state applies them (version-memoized) and a settings
    removal re-applies the documented defaults."""
    from elasticsearch_tpu.search.device_profile import DEVICE_PROFILE
    old = (DEVICE_PROFILE.storm_threshold, DEVICE_PROFILE.storm_window_s,
           DEVICE_PROFILE.slow_compile_ms)
    try:
        DEVICE_PROFILE.configure_from_state(_fake_state(101, {
            "search.device_profile.storm_threshold": 3,
            "search.device_profile.storm_window": "10s",
            "search.device_profile.slow_compile_threshold": "250ms"}))
        assert DEVICE_PROFILE.storm_threshold == 3
        assert DEVICE_PROFILE.storm_window_s == 10.0
        assert DEVICE_PROFILE.slow_compile_ms == 250.0
        # same version: memoized, no re-read
        DEVICE_PROFILE.storm_threshold = 99
        DEVICE_PROFILE.configure_from_state(_fake_state(101, {}))
        assert DEVICE_PROFILE.storm_threshold == 99
        # new version without the keys: defaults return
        DEVICE_PROFILE.configure_from_state(_fake_state(102, {}))
        assert DEVICE_PROFILE.storm_threshold == 8
        assert DEVICE_PROFILE.storm_window_s == 60.0
        assert DEVICE_PROFILE.slow_compile_ms == 1000.0
    finally:
        DEVICE_PROFILE._cfg_version = object()
        (DEVICE_PROFILE.storm_threshold, DEVICE_PROFILE.storm_window_s,
         DEVICE_PROFILE.slow_compile_ms) = old


def test_plane_rerank_depth_max_from_state():
    PLANES.configure_from_state(_fake_state(201, {
        "search.plane.rerank_depth_max": 256}))
    assert PLANES.rerank_depth_max == 256
    PLANES.configure_from_state(_fake_state(202, {}))
    assert PLANES.rerank_depth_max == 1024
    PLANES._cfg_version = object()


def test_stats_surface_carries_tier_counters():
    snap = PLANES.stats_snapshot()
    for key in ("quantized_queries", "rerank_escalations",
                "quantized_exact_fallbacks", "rerank_depth_histogram",
                "rerank_depth_max"):
        assert key in snap
    msnap = MESH_PLANES.stats_snapshot()
    for key in ("mesh_quantized_queries", "mesh_quantized_mirror_builds",
                "mesh_quantized_fallbacks"):
        assert key in msnap


# ---------------------------------------------------------------------------
# seed sweep (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("seed", [171 + 13 * k
                                  for k in range(max(5, CHAOS_SEEDS))])
def test_quantized_parity_sweep_slow(seed):
    eng, rng = _engine(seed)
    reader = eng.acquire_reader()
    for body in _bodies(rng):
        for track in (10_000, 5, False):
            PLANES.quantized = False
            exact = _run(eng, reader, body, track=track)
            PLANES.quantized = True
            quant = _run(eng, reader, body, track=track)
            _assert_same(exact, quant)
