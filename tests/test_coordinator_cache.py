"""Coordinator fused-result cache: identical fan-outs skip shard
dispatch entirely (indices/request_cache.py FusedResultCache).

Contracts under test:

- a duplicate identical fan-out over unmoved shard generations answers
  from the coordinator with ZERO shard dispatches and ZERO device
  dispatches, byte-identical (modulo took/_data_plane) to the uncached
  execution — on the batch/fan-out path AND the mesh-served path;
- the entry is stamped with the participating shards' generation
  VECTOR: the moment ONE shard of the fan-out refreshes, the duplicate
  misses, re-executes, and the invalidation is typed by the moved
  shard's cause;
- the cache engages only for co-located fan-outs (every target shard
  locally present — the only shape whose generations the coordinator
  can read without an RPC); anything else counts ``not_colocated`` and
  serves uncached;
- hits are labeled with the ``cached`` data plane in telemetry, so the
  win is observable end-to-end;
- the adaptive per-copy shard-query transport timeout (the PR 13
  recorded leg) rides along: RTT-scale failover off the ARS response
  EWMA, floor/ceiling settings, request-budget bound.
"""

import json
import os

import numpy as np
import pytest

from elasticsearch_tpu.testing import InProcessCluster

CHAOS_SEEDS = int(os.environ.get("CHAOS_SEEDS", "1") or "1")

pytestmark = pytest.mark.cache


def _ok(resp, err):
    assert err is None, f"unexpected error: {err}"
    return resp


def _strip(resp):
    return {k: v for k, v in resp.items()
            if k not in ("took", "_data_plane")}


def _settings(c, values):
    _ok(*c.call(lambda cb: c.client().cluster_update_settings(
        {"persistent": values}, cb)))


def _search(c, index, body, node="node0"):
    return _ok(*c.call(lambda cb: c.nodes[node].client.search(
        index, json.loads(json.dumps(body)), cb)))


def _build_cluster(seed, n_nodes=1, shards=2, replicas=0, docs=48):
    c = InProcessCluster(n_nodes=n_nodes, seed=seed)
    c.start()
    client = c.client()
    _ok(*c.call(lambda cb: client.create_index("cc", {
        "settings": {"number_of_shards": shards,
                     "number_of_replicas": replicas},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "brand": {"type": "keyword"}}}}, cb)))
    c.ensure_green("cc")
    rng = np.random.default_rng(seed)
    for i in range(docs):
        _ok(*c.call(lambda cb, i=i: client.index_doc(
            "cc", f"d{i}",
            {"body": " ".join(f"w{int(x)}"
                              for x in rng.integers(0, 16, 6)),
             "brand": f"b{i % 3}"}, cb)))
    c.call(lambda cb: client.refresh("cc", cb))
    _settings(c, {"search.request_cache.topk": True})
    return c


def _device_dispatches():
    from elasticsearch_tpu.search.telemetry import TELEMETRY
    return sum(entry["dispatches"]
               for entry in TELEMETRY._planes.values())


# ---------------------------------------------------------------------------
# duplicate fan-out skips shard dispatch entirely
# ---------------------------------------------------------------------------

def _duplicate_fanout_case(seed):
    c = _build_cluster(seed)
    try:
        node = c.nodes["node0"]
        fused = node.search_action.fused_cache
        batcher = node.search_transport.batcher
        body = {"query": {"match": {"body": "w1 w2"}}, "size": 6,
                "track_total_hits": True,
                "aggs": {"b": {"terms": {"field": "brand"}}}}
        first = _strip(_search(c, "cc", body))
        dispatched0 = batcher.stats["queries_dispatched"]
        intake0 = batcher.stats["request_cache_intake_hits"]
        dev0 = _device_dispatches()
        hits0 = fused.stats["hits"]
        dup = _strip(_search(c, "cc", body))
        assert dup == first
        assert fused.stats["hits"] == hits0 + 1
        # the duplicate never reached a shard, a drain, or the device
        assert batcher.stats["queries_dispatched"] == dispatched0
        assert batcher.stats["request_cache_intake_hits"] == intake0
        assert _device_dispatches() == dev0
        # golden vs a per-request opt-out (uncached execution)
        uncached = _strip(_search(c, "cc",
                                  {**body, "request_cache": False}))
        assert dup == uncached
        # observable end-to-end: the hit landed in the "cached" plane
        from elasticsearch_tpu.search.telemetry import TELEMETRY
        assert any(plane == "cached"
                   for _cls, plane in TELEMETRY._planes), \
            sorted(TELEMETRY._planes)
    finally:
        c.stop()


@pytest.mark.parametrize("seed", [307 + 881 * k for k in range(CHAOS_SEEDS)])
def test_duplicate_fanout_served_from_coordinator(seed):
    _duplicate_fanout_case(seed)


@pytest.mark.slow
def test_duplicate_fanout_seed_sweep():
    for k in range(max(CHAOS_SEEDS, 5)):
        _duplicate_fanout_case(307 + 881 * k)


# ---------------------------------------------------------------------------
# one shard's generation moving invalidates the whole fused entry
# ---------------------------------------------------------------------------

def test_one_shard_refresh_invalidates_fused_entry():
    c = _build_cluster(409, shards=3)
    try:
        client = c.client()
        node = c.nodes["node0"]
        fused = node.search_action.fused_cache
        body = {"query": {"match": {"body": "w3"}}, "size": 5,
                "track_total_hits": True}
        first = _search(c, "cc", body)
        hits0 = fused.stats["hits"]
        _search(c, "cc", body)
        assert fused.stats["hits"] == hits0 + 1

        # one more matching doc lands on ONE shard of the fan-out; the
        # refresh moves only that shard's generation
        gens_before = [node.indices_service.shard("cc", s).search_generation
                       for s in range(3)]
        _ok(*c.call(lambda cb: client.index_doc(
            "cc", "extra", {"body": "w3 w3", "brand": "b0"}, cb)))
        c.call(lambda cb: client.refresh("cc", cb))
        gens_after = [node.indices_service.shard("cc", s).search_generation
                      for s in range(3)]
        moved = sum(1 for a, b in zip(gens_before, gens_after) if a != b)
        assert 1 <= moved < 3

        inv0 = sum(fused.invalidations_by_cause.values())
        fresh = _search(c, "cc", body)
        assert fused.stats["hits"] == hits0 + 1          # a miss
        assert sum(fused.invalidations_by_cause.values()) == inv0 + 1
        assert fused.invalidations_by_cause.get("unknown", 0) == 0
        assert fresh["hits"]["total"]["value"] == \
            first["hits"]["total"]["value"] + 1
        # and the refilled entry serves the NEW result
        again = _strip(_search(c, "cc", body))
        assert again == _strip(fresh)
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# mesh-path parity: first served mesh, duplicate served cached
# ---------------------------------------------------------------------------

def test_mesh_served_fanout_duplicate_cached_identical():
    c = _build_cluster(521, shards=2)
    try:
        node = c.nodes["node0"]
        fused = node.search_action.fused_cache
        body = {"query": {"match": {"body": "w5 w6"}}, "size": 5}
        first = _search(c, "cc", body)
        # a co-located 2-shard text fan-out is mesh-eligible; whichever
        # plane served, the duplicate must byte-match it modulo
        # took/_data_plane with zero additional shard work
        hits0 = fused.stats["hits"]
        dup = _search(c, "cc", body)
        assert fused.stats["hits"] == hits0 + 1
        assert _strip(dup) == _strip(first)
        assert dup["_data_plane"] == "cached"   # the hit names itself
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# co-location gate: a fan-out with remote shards serves uncached
# ---------------------------------------------------------------------------

def test_not_colocated_fanout_serves_uncached():
    c = _build_cluster(613, n_nodes=3, shards=3)
    try:
        # find a coordinator that does NOT hold every shard locally
        coord = None
        for nid, node in c.nodes.items():
            held = sum(1 for s in range(3)
                       if node.indices_service.has_shard("cc", s))
            if held < 3:
                coord = nid
                break
        assert coord is not None, "every node holds every shard"
        fused = c.nodes[coord].search_action.fused_cache
        body = {"query": {"match": {"body": "w2"}}, "size": 4}
        nc0 = fused.stats["not_colocated"]
        r1 = _strip(_search(c, "cc", body, node=coord))
        r2 = _strip(_search(c, "cc", body, node=coord))
        assert r1 == r2
        assert fused.stats["not_colocated"] > nc0
        assert fused.stats["hits"] == 0
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# fleet-harness-shaped traffic: duplicate-heavy multi-coordinator storm
# ---------------------------------------------------------------------------

def test_duplicate_heavy_multi_coordinator_traffic_stays_correct():
    c = _build_cluster(719, n_nodes=2, shards=1, replicas=1, docs=24)
    try:
        bodies = [{"query": {"match": {"body": f"w{i % 4}"}},
                   "size": 5, "track_total_hits": True}
                  for i in range(4)]
        # baselines, per body, uncached by per-request opt-out
        base = [_strip(_search(c, "cc", {**b, "request_cache": False}))
                for b in bodies]
        boxes = []
        for i in range(40):
            body = bodies[i % len(bodies)]
            nid = f"node{i % 2}"
            box = []
            c.nodes[nid].client.search(
                "cc", json.loads(json.dumps(body)),
                lambda resp, err=None, b=box: b.append((resp, err)))
            boxes.append((i, box))
        c.run_until(lambda: all(b for _i, b in boxes), 300.0)
        served_cached = 0
        for i, box in boxes:
            resp = _ok(*box[0])
            assert _strip(resp) == base[i % len(bodies)], i
        for nid in ("node0", "node1"):
            node = c.nodes[nid]
            served_cached += node.search_action.fused_cache.stats["hits"]
            served_cached += node.search_transport.batcher.stats[
                "request_cache_intake_hits"]
        assert served_cached > 0
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# adaptive per-copy shard-query transport timeout (PR 13 recorded leg)
# ---------------------------------------------------------------------------

def test_adaptive_timeout_units():
    from elasticsearch_tpu.action.search_action import (
        TransportSearchAction,
    )
    from elasticsearch_tpu.action.response_collector import (
        ResponseCollectorService,
    )
    action = TransportSearchAction.__new__(TransportSearchAction)
    action.response_collector = ResponseCollectorService()
    # unknown copy: the ceiling (the old flat 60s)
    assert action._shard_query_timeout("n1", 2.0, 60.0, None) == 60.0
    # fast copy: 30x EWMA, floored
    action.response_collector.on_send("n1")
    action.response_collector.on_response("n1", 0.010)
    assert action._shard_query_timeout("n1", 2.0, 60.0, None) == 2.0
    # LAST copy (nothing to fail over to): the ceiling, always —
    # abandoning a slow-but-alive only copy converts success to failure
    assert action._shard_query_timeout("n1", 2.0, 60.0, None,
                                       has_failover=False) == 60.0
    # slow copy: 30x EWMA inside the band
    action.response_collector.on_send("n2")
    action.response_collector.on_response("n2", 0.5)
    t = action._shard_query_timeout("n2", 2.0, 60.0, None)
    assert 10.0 <= t <= 20.0
    # ceiling clamps a pathological EWMA
    action.response_collector.on_send("n3")
    action.response_collector.on_response("n3", 30.0)
    assert action._shard_query_timeout("n3", 2.0, 60.0, None) == 60.0
    # the request's own budget bounds every copy's wait — landing
    # strictly AFTER the budget timer (+50ms) so an expiry surfaces as
    # the timed_out partial, never a same-instant copy-timeout race
    assert abs(action._shard_query_timeout(
        "n1", 2.0, 60.0, 0.25) - 0.30) < 1e-9
    assert abs(action._shard_query_timeout(
        "n1", 2.0, 60.0, 0.0) - 0.05) < 1e-9


def test_stalled_copy_fails_over_in_rtt_scale_time():
    """A known-fast copy that goes silent (drop rule) is abandoned at
    the adaptive timeout — the floor, not the 60s ceiling — and the
    sibling copy serves."""
    c = _build_cluster(823, n_nodes=2, shards=1, replicas=1, docs=12)
    try:
        # pure rotation: the silent copy leads the list on alternating
        # searches, so the adaptive timeout is genuinely exercised
        _settings(c, {"search.shard.query_timeout.floor": 0.5,
                      "cluster.routing.use_adaptive_replica_selection":
                          False})
        body = {"query": {"match": {"body": "w1"}}, "size": 3,
                "request_cache": False}
        # warm every copy's EWMA so both rank as known-fast
        for _ in range(4):
            _search(c, "cc", body)
        # one copy-holder goes silent for search traffic
        holders = [nid for nid, n in c.nodes.items()
                   if n.indices_service.has_shard("cc", 0)]
        assert len(holders) == 2
        victim = [nid for nid in holders if nid != "node0"][0]
        c.partition_one_way(["node0"], [victim])
        t0 = c.scheduler.now()
        for _ in range(4):
            got = _search(c, "cc", body)
            assert got["hits"]["hits"], got
        elapsed = c.scheduler.now() - t0
        # the FIRST victim-led search failed over at the ~0.5s floor;
        # the timeout-as-failure EWMA inflation then widens later waits
        # (self-correcting toward the ceiling, never past it). Under the
        # old flat 60s transport timeout this loop costs >= 120s of
        # virtual time — the bound pins the RTT-scale win with margin.
        assert elapsed < 30.0, elapsed
    finally:
        c.heal()
        c.stop()
