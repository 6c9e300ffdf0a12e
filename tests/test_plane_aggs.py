"""Drain-wide device aggregations: golden parity + chaos cases.

The columns plane (ops/device_segment.py PlaneColumns) and the drain
planner (search/plane_aggs.py) must be invisible in results: for every
shape the plane kernels serve (sub-less keyword terms, integral-interval
histograms with same-field metric subs), the whole-shard partials preset
into the ShardAggregator are byte-identical to what the host per-segment
collectors fold — under deletes, refresh-during-query with point-in-time
readers, eviction, and a starved breaker. Occupancy never changes
results, dispatches per (shard, agg family) stay at one regardless of
segment count AND distinct-plan count, and every fallback is typed
(the "unknown" bucket stays pinned at zero).
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from elasticsearch_tpu.index import InternalEngine
from elasticsearch_tpu.indices.breaker import BREAKERS
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.ops.device_segment import PLANES
from elasticsearch_tpu.search import dsl, telemetry
from elasticsearch_tpu.search.aggregations import ShardAggregator, parse_aggs
from elasticsearch_tpu.search.device_profile import DEVICE_PROFILE
from elasticsearch_tpu.search.phase import parse_sort, query_shard
from elasticsearch_tpu.search.plane_aggs import plan_drain_aggs

# CHAOS_SEEDS=N widens the seeded sweeps, like the other chaos suites
CHAOS_SEEDS = int(os.environ.get("CHAOS_SEEDS", "1") or "1")

pytestmark = pytest.mark.aggs_plane


@pytest.fixture(autouse=True)
def _plane_defaults():
    PLANES.clear()
    PLANES.enabled = True
    PLANES.min_segments = 2
    PLANES.max_bytes = 0
    yield
    PLANES.clear()
    PLANES.enabled = True
    PLANES.max_bytes = 0


def _engine(seed: int, n_docs: int = 220, cuts=(70, 140)):
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(30)]
    eng = InternalEngine(
        MapperService({"properties": {
            "body": {"type": "text"},
            "tag": {"type": "keyword"},
            "rank": {"type": "integer"},
            "price": {"type": "integer"}}}),
        shard_label=f"pa{seed}")
    for i in range(n_docs):
        doc = {"body": " ".join(rng.choice(
                   vocab, size=int(rng.integers(3, 14)))),
               "rank": int(rng.integers(0, 60))}
        if i % 11:      # some docs miss tag/price: exists-mask parity
            doc["tag"] = f"t{int(rng.integers(0, 9))}"
        if i % 7:
            doc["price"] = int(rng.integers(-40, 400))
        eng.index(str(i), doc)
        if i in cuts:
            eng.refresh()
    eng.refresh()
    return eng, rng


# terms + histogram + same-field metric subs: every plane-served family
AGGS = {
    "tags": {"terms": {"field": "tag", "size": 10}},
    "ranks": {"histogram": {"field": "rank", "interval": 7}},
    "prices": {"histogram": {"field": "price", "interval": 25},
               "aggs": {"lo": {"min": {"field": "price"}},
                        "hi": {"max": {"field": "price"}},
                        "mean": {"avg": {"field": "price"}},
                        "n": {"value_count": {"field": "price"}}}},
}

QUERIES = [{"match": {"body": "w1 w2"}},
           {"match_all": {}},
           {"term": {"tag": "t1"}}]


def _member(qbody, aggs=AGGS):
    return SimpleNamespace(
        req={"index": "i", "shard": 0, "window": 10,
             "body": {"query": qbody, "aggs": aggs}},
        trace=None, error=None)


def _host_partials(eng, reader, qbody, aggs=AGGS):
    """The reference: host per-segment collection through query_shard,
    exactly the path an unpreset member runs."""
    agg = ShardAggregator(parse_aggs(aggs))
    query_shard(reader, eng.mappers, dsl.parse_query(qbody), size=5,
                sort=parse_sort(None), track_total_hits=10_000,
                collectors=[agg])
    return agg.partial()


def _jeq(a, b):
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True), \
        (a, b)


def _assert_drain_parity(eng, reader, queries=QUERIES, aggs=AGGS):
    shard = SimpleNamespace(engine=eng)
    members = [_member(q, aggs) for q in queries]
    preset = plan_drain_aggs(shard, reader, members)
    assert set(preset) == set(range(len(members))), preset.keys()
    for ui, m in enumerate(members):
        host = _host_partials(eng, reader, m.req["body"]["query"], aggs)
        assert set(preset[ui]) == set(aggs)
        for name in preset[ui]:
            _jeq(preset[ui][name], host[name])
    return preset


# ---------------------------------------------------------------------------
# golden parity: plane partials vs host collectors, all served shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [41 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_golden_terms_hist_subagg_parity(seed):
    eng, rng = _engine(seed)
    reader = eng.acquire_reader()
    q0 = PLANES.stats["plane_aggs_queries"]
    _assert_drain_parity(eng, reader)
    assert PLANES.stats["plane_aggs_queries"] - q0 == \
        len(QUERIES) * len(AGGS)
    assert PLANES.stats_snapshot()["resident_bytes"]["columns"] > 0
    assert telemetry.TELEMETRY.fallbacks.get("unknown", 0) == 0


@pytest.mark.parametrize("seed", [43 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_golden_parity_with_deletes(seed):
    eng, rng = _engine(seed)
    for i in rng.choice(200, size=35, replace=False):
        eng.delete(str(int(i)))
    eng.refresh()
    reader = eng.acquire_reader()
    _assert_drain_parity(eng, reader)


@pytest.mark.parametrize("seed", [47 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_pit_reader_refresh_during_query_parity(seed):
    """Refresh-during-query with a point-in-time reader: the drain mask
    cache must never hand a PIT reader (older live set) a mask baked
    under a NEWER, smaller live set — the live-count-in-key rule."""
    eng, rng = _engine(seed)
    shard = SimpleNamespace(engine=eng)
    pit = eng.acquire_reader()
    qbody = {"match": {"body": "w1"}}
    # warm the plane + mask cache under the pre-delete live set
    plan_drain_aggs(shard, pit, [_member(qbody)])
    for i in rng.choice(200, size=40, replace=False):
        eng.delete(str(int(i)))
    eng.refresh()
    post = eng.acquire_reader()
    # post-delete reader: parity under the shrunk live set
    _assert_drain_parity(eng, post, queries=[qbody])
    # the PIT reader still sees every pre-delete doc: parity again, NOT
    # the post-delete cached masks
    _assert_drain_parity(eng, pit, queries=[qbody])
    pit_counts = _host_partials(eng, pit, {"match_all": {}})
    post_counts = _host_partials(eng, post, {"match_all": {}})
    assert json.dumps(pit_counts, sort_keys=True) != \
        json.dumps(post_counts, sort_keys=True)   # the case genuinely bites


# ---------------------------------------------------------------------------
# occupancy + dispatch accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [53 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_occupancy_invariance_and_single_dispatch_per_family(seed):
    """A drain of N agg members produces the same partials as N drains
    of one — and the N-member drain costs ONE device dispatch per
    (shard, agg family), even with a distinct histogram interval per
    member (per-plan base/interval ride as traced vectors)."""
    eng, rng = _engine(seed)
    reader = eng.acquire_reader()
    shard = SimpleNamespace(engine=eng)
    members = [
        _member({"match": {"body": f"w{j}"}},
                aggs={"tags": {"terms": {"field": "tag"}},
                      "ranks": {"histogram": {"field": "rank",
                                              "interval": 5 + j}}})
        for j in range(4)]
    plan_drain_aggs(shard, reader, members)   # warm plane + compile cache

    def family_calls():
        t = DEVICE_PROFILE.family("aggs_ordinal_counts_plane")
        h = DEVICE_PROFILE.family("aggs_histogram_plane")
        return (t.compiles + t.cache_hits, h.compiles + h.cache_hits)

    c0 = family_calls()
    batch = plan_drain_aggs(shard, reader, members)
    c1 = family_calls()
    assert c1[0] - c0[0] == 1, "terms: one dispatch at occupancy 4"
    assert c1[1] - c0[1] == 1, "hist: one dispatch across 4 intervals"
    for ui, m in enumerate(members):
        solo = plan_drain_aggs(shard, reader, [m])
        _jeq(batch[ui], solo[0])


# ---------------------------------------------------------------------------
# lifecycle chaos: eviction, incremental append, starved breaker
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [59 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_eviction_then_rebuild_and_incremental_append(seed):
    eng, rng = _engine(seed)
    reader = eng.acquire_reader()
    first = _assert_drain_parity(eng, reader, queries=[QUERIES[0]])
    ev0 = PLANES.stats["plane_evictions"]
    PLANES.evict_cold()
    assert PLANES.stats["plane_evictions"] > ev0
    second = _assert_drain_parity(eng, reader, queries=[QUERIES[0]])
    _jeq(first, second)
    # refresh-append: new docs in a new segment ride the incremental
    # build path (prev plane is a uid-prefix), parity intact
    for i in range(300, 340):
        eng.index(str(i), {"body": "w1 appended", "tag": "t_new",
                           "rank": 61, "price": 401})
    eng.refresh()
    appends0 = PLANES.stats["plane_incremental_appends"]
    _assert_drain_parity(eng, eng.acquire_reader())
    assert PLANES.stats["plane_incremental_appends"] > appends0


@pytest.mark.parametrize("seed", [61 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_breaker_starved_fallback_identity(seed):
    """A request breaker with no transient headroom refuses the mask
    stack: the drain presets NOTHING (typed plane_aggs_breaker_refused),
    members keep the host path — and the host partials are the same
    ones the plane would have preset."""
    eng, rng = _engine(seed)
    reader = eng.acquire_reader()
    shard = SimpleNamespace(engine=eng)
    want = _assert_drain_parity(eng, reader)   # plane resident + parity
    req = BREAKERS.breaker("request")
    old_limit = req.limit
    fb0 = PLANES.stats["plane_aggs_fallbacks"]
    typed0 = telemetry.TELEMETRY.fallbacks.get(
        "plane_aggs_breaker_refused", 0)
    try:
        req.limit = req.used + 16
        preset = plan_drain_aggs(shard, reader,
                                 [_member(q) for q in QUERIES])
    finally:
        req.limit = old_limit
    assert preset == {}, preset
    assert PLANES.stats["plane_aggs_fallbacks"] > fb0
    assert telemetry.TELEMETRY.fallbacks.get(
        "plane_aggs_breaker_refused", 0) > typed0
    # identity: what the members now compute on the host path is exactly
    # what the plane preset before the breaker starved
    for ui, q in enumerate(QUERIES):
        host = _host_partials(eng, reader, q)
        for name in want[ui]:
            _jeq(want[ui][name], host[name])
    assert telemetry.TELEMETRY.fallbacks.get("unknown", 0) == 0


# ---------------------------------------------------------------------------
# typed fallback taxonomy: ineligible shapes, no unknown bucket
# ---------------------------------------------------------------------------

def test_ineligible_shapes_keep_host_path_typed():
    eng, rng = _engine(67)
    reader = eng.acquire_reader()
    shard = SimpleNamespace(engine=eng)
    ineligible = [
        # terms with subs / missing; off-field metric sub; min_score body
        _member(QUERIES[0], aggs={"a": {"terms": {
            "field": "tag"}, "aggs": {"m": {"avg": {"field": "rank"}}}}}),
        _member(QUERIES[0], aggs={"a": {"terms": {
            "field": "tag", "missing": "zz"}}}),
        _member(QUERIES[0], aggs={"a": {"histogram": {
            "field": "rank", "interval": 5},
            "aggs": {"m": {"avg": {"field": "price"}}}}}),
    ]
    shape0 = telemetry.TELEMETRY.fallbacks.get(
        "plane_aggs_ineligible_shape", 0)
    preset = plan_drain_aggs(shard, reader, ineligible)
    assert preset == {}, preset
    assert telemetry.TELEMETRY.fallbacks.get(
        "plane_aggs_ineligible_shape", 0) > shape0
    # a member with shard-stat overrides is member-ineligible
    m = _member(QUERIES[0])
    m.req["df_overrides"] = {"body": {"w1": 3}}
    assert plan_drain_aggs(shard, reader, [m]) == {}
    assert telemetry.TELEMETRY.fallbacks.get("unknown", 0) == 0


# ---------------------------------------------------------------------------
# end to end: dense_device label + response-level byte identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [71 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_cluster_parity_and_dense_device_label(seed):
    """Full path through the cluster: plane-off and plane-on responses
    identical (hits AND aggregations) but for their ``_data_plane``,
    which names the member's plane ("dense" on the host, "dense_device"
    on the columns plane) as the latency histograms do."""
    from elasticsearch_tpu.testing import InProcessCluster
    c = InProcessCluster(n_nodes=1, seed=seed)
    c.start()
    try:
        client = c.client()
        box = []
        client.create_index("ix", {
            "settings": {"number_of_shards": 1,
                         "number_of_replicas": 0},
            "mappings": {"properties": {
                "body": {"type": "text"}, "tag": {"type": "keyword"},
                "rank": {"type": "integer"}}}},
            lambda r, e=None: box.append((r, e)))
        c.run_until(lambda: bool(box), 60.0)
        c.ensure_green("ix")
        rng = np.random.default_rng(seed)
        for i in range(150):
            done = []
            client.index_doc("ix", f"d{i}", {
                "body": " ".join(rng.choice(
                    [f"w{k}" for k in range(25)],
                    size=int(rng.integers(3, 10)))),
                "tag": f"t{i % 6}", "rank": int(rng.integers(0, 50))},
                lambda r, e=None: done.append(1))
            c.run_until(lambda: bool(done), 60.0)
            if i in (50, 100):
                c.call(lambda cb: client.refresh("ix", cb))
        c.call(lambda cb: client.refresh("ix", cb))

        def set_plane(v):
            ok = []
            client.cluster_update_settings(
                {"persistent": {"search.plane.enabled": v}},
                lambda r, e=None: ok.append((r, e)))
            c.run_until(lambda: bool(ok), 60.0)

        def search(b):
            got = []
            client.search("ix", b,
                          lambda r, e=None: got.append((r, e)))
            c.run_until(lambda: bool(got), 120.0)
            resp, err = got[0]
            assert err is None, err
            return resp

        def strip(resp):
            return {k: v for k, v in resp.items()
                    if k not in ("took", "_data_plane")}

        def dense_obs():
            # TELEMETRY is process-global: earlier tests may already
            # have minted a dense_device key, so assert GROWTH not
            # key novelty
            return sum(e["queries"]
                       for k, e in telemetry.TELEMETRY._planes.items()
                       if k[1] == "dense_device")

        body = {"query": {"match": {"body": "w1 w2 w3"}}, "size": 5,
                "aggs": AGGS}
        set_plane(False)
        host = search(body)
        q_off = PLANES.stats["plane_aggs_queries"]
        set_plane(True)
        obs0 = dense_obs()
        dev = search(dict(body))
        _jeq(strip(host), strip(dev))
        assert PLANES.stats["plane_aggs_queries"] > q_off
        assert dense_obs() > obs0, dict(telemetry.TELEMETRY._planes)
        assert host["_data_plane"] == "dense"
        assert dev["_data_plane"] == "dense_device"
        assert telemetry.TELEMETRY.fallbacks.get("unknown", 0) == 0
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# CI seed sweep
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_aggs_plane_seed_sweep():
    """>= 5 seeded RNGs through the full parity battery (CHAOS_SEEDS
    widens it further), deletes included."""
    for k in range(max(CHAOS_SEEDS, 5)):
        seed = 41 + 977 * k
        PLANES.clear()
        eng, rng = _engine(seed)
        _assert_drain_parity(eng, eng.acquire_reader())
        for i in rng.choice(200, size=30, replace=False):
            eng.delete(str(int(i)))
        eng.refresh()
        _assert_drain_parity(eng, eng.acquire_reader())
