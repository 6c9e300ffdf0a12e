"""Solo is a batch of one: the unified shard execution path.

Every shard query — including the shapes the device demux cannot batch
(aggregations, suggest, nested, spans, rescore, collapse, profile) —
rides ShardQueryBatcher as a ``dense`` member: device work per member,
but the drain's reader acquisition, per-drain memo, and collection
window are shared. These tests pin the refactor's contracts:

- newly-batched shapes return byte-identical responses at any drain
  occupancy (coalesced wave == one-at-a-time), CHAOS_SEEDS-swept;
- ``search.batch.enabled: false`` forces window 0 through the SAME
  path, byte-identical responses;
- deadline expiry / cancellation mid-drain fails a dense member
  individually, batch-mates unaffected;
- the deleted solo kernels and dual-path plumbing STAY deleted (a
  grep-style guard over the package source);
- `_tasks` phase fidelity: occupancy-1 members surface the
  dispatch/demux sub-phases, not "query" for their whole life;
- the request cache answers cacheable duplicates AT INTAKE (no
  collection-window wait), and per-key max_size adapts under HBM
  pressure.
"""

import json
import os
import re
from pathlib import Path

import numpy as np
import pytest

from elasticsearch_tpu.testing import InProcessCluster

CHAOS_SEEDS = int(os.environ.get("CHAOS_SEEDS", "1") or "1")


def _ok(resp, err):
    assert err is None, f"unexpected error: {err}"
    return resp


@pytest.fixture(scope="module")
def cluster():
    c = InProcessCluster(n_nodes=1, seed=53)
    c.start()
    client = c.client()
    _ok(*c.call(lambda cb: client.create_index("ux", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "brand": {"type": "keyword"},
            "price": {"type": "integer"},
            "comments": {"type": "nested", "properties": {
                "author": {"type": "keyword"},
                "text": {"type": "text"}}},
            "vec": {"type": "dense_vector", "dims": 8}}}}, cb)))
    c.ensure_green("ux")
    rng = np.random.default_rng(29)
    vocab = [f"w{i}" for i in range(30)]
    authors = ["amy", "bob", "cal"]
    for i in range(90):
        doc = {"body": " ".join(rng.choice(
                   vocab, size=int(rng.integers(4, 16)))),
               "brand": f"b{i % 4}",
               "price": int(rng.integers(1, 50)),
               "comments": [{"author": authors[i % 3],
                             "text": f"w{i % 7} comment"}],
               "vec": [float(x) for x in rng.standard_normal(8)]}
        _ok(*c.call(lambda cb, i=i, doc=doc: client.index_doc(
            "ux", f"d{i}", doc, cb)))
    c.call(lambda cb: client.refresh("ux", cb))
    yield c
    c.stop()


def _shape_bodies(rng):
    """One body per previously-solo-only shape (each classifies to the
    ``dense`` member kind)."""
    w = lambda: f"w{int(rng.integers(0, 30))}"  # noqa: E731
    return {
        "aggs": {"query": {"match": {"body": f"{w()} {w()}"}}, "size": 4,
                 "aggs": {"brands": {"terms": {"field": "brand"}},
                          "p": {"avg": {"field": "price"}}}},
        "suggest": {"size": 0, "suggest": {"s": {
            "text": w()[:-1] or "w", "term": {"field": "body"}}}},
        "nested": {"query": {"nested": {
            "path": "comments",
            "query": {"term": {"comments.author": "amy"}}}}, "size": 5},
        "spans": {"query": {"span_near": {
            "clauses": [{"span_term": {"body": w()}},
                        {"span_term": {"body": w()}}],
            "slop": 12, "in_order": False}}, "size": 5},
        "rescore": {"query": {"match": {"body": f"{w()} {w()}"}},
                    "size": 4,
                    "rescore": {"window_size": 10, "query": {
                        "rescore_query": {"match": {"body": w()}},
                        "query_weight": 1.0,
                        "rescore_query_weight": 2.0}}},
        "collapse": {"query": {"match": {"body": f"{w()} {w()}"}},
                     "size": 4, "collapse": {"field": "brand"}},
    }


def _wave(c, bodies):
    client = c.client()
    boxes = []
    for b in bodies:
        box = []
        client.search("ux", json.loads(json.dumps(b)),
                      lambda resp, err=None, box=box: box.append(
                          (resp, err)))
        boxes.append(box)
    c.run_until(lambda: all(boxes), 120.0)
    return [_ok(*box[0]) for box in boxes]


def _strip(resp):
    # _data_plane: a coordinator-cache hit says "cached"
    return {k: v for k, v in resp.items() if k not in ("took", "_data_plane")}


# ---------------------------------------------------------------------------
# newly-batched shapes: occupancy-N == occupancy-1, byte-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [7 + 991 * k for k in range(CHAOS_SEEDS)])
def test_newly_batched_shapes_golden_parity(cluster, seed):
    """Each previously-ineligible shape produces byte-identical
    responses whether its drain coalesced a concurrent wave (duplicates
    included — the per-drain memo fans rows out) or ran it alone."""
    c = cluster
    client = c.client()
    batcher = c.nodes["node0"].search_transport.batcher
    rng = np.random.default_rng(seed)
    shapes = _shape_bodies(rng)

    solo = {}
    for name, body in shapes.items():
        solo[name] = _strip(_ok(*c.call(
            lambda cb, b=body: client.search(
                "ux", json.loads(json.dumps(b)), cb))))

    before = dict(batcher.stats)
    # one concurrent wave: every shape plus a duplicate of each — all
    # dense members share the shard's one dense queue, so the whole
    # wave is one drain (shared reader acquisition, memo dedup)
    wave_bodies = list(shapes.values()) + list(shapes.values())
    wave = _wave(c, wave_bodies)
    assert batcher.stats["max_occupancy"] >= \
        max(before["max_occupancy"], 2)
    assert batcher.stats["memo_hits"] > before["memo_hits"]

    names = list(shapes) + list(shapes)
    for name, resp in zip(names, wave):
        assert _strip(resp) == solo[name], name


def test_enabled_false_is_window_zero_same_path(cluster):
    """``search.batch.enabled: false`` must not grow back a second
    execution path: responses stay byte-identical, and the batcher's
    counters keep moving (window 0, same code)."""
    c = cluster
    client = c.client()
    batcher = c.nodes["node0"].search_transport.batcher
    rng = np.random.default_rng(3)
    shapes = _shape_bodies(rng)
    enabled = {n: _strip(_ok(*c.call(
        lambda cb, b=b: client.search("ux", json.loads(json.dumps(b)),
                                      cb)))) for n, b in shapes.items()}
    _ok(*c.call(lambda cb: client.cluster_update_settings(
        {"persistent": {"search.batch.enabled": False}}, cb)))
    try:
        fused = c.nodes["node0"].search_action.fused_cache
        before = dict(batcher.stats)
        fused_before = fused.stats["hits"]
        for name, body in shapes.items():
            got = _strip(_ok(*c.call(
                lambda cb, b=body: client.search(
                    "ux", json.loads(json.dumps(b)), cb))))
            assert got == enabled[name], name
        # every shape still rode the batcher (the size-0 suggest shape
        # may answer from a request-cache tier instead: the batcher's
        # intake consult, or the coordinator fused-result cache before
        # the shard is even dispatched)
        served = (batcher.stats["queries_dispatched"]
                  - before["queries_dispatched"]) + \
                 (batcher.stats["request_cache_intake_hits"]
                  - before["request_cache_intake_hits"]) + \
                 (fused.stats["hits"] - fused_before)
        assert served >= len(shapes)
    finally:
        _ok(*c.call(lambda cb: client.cluster_update_settings(
            {"persistent": {"search.batch.enabled": None}}, cb)))


# ---------------------------------------------------------------------------
# deadline / cancellation mid-drain for dense members
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [61 + 1000 * k for k in range(CHAOS_SEEDS)])
def test_deadline_and_cancel_mid_drain_aggs_member(cluster, seed):
    """An aggregations member whose budget expired while queued (and a
    cancelled one) fail INDIVIDUALLY at drain entry; dense batch-mates
    complete with correct aggregation partials."""
    c = cluster
    sts = c.nodes["node0"].search_transport
    batcher = sts.batcher
    rng = np.random.default_rng(seed)
    n = 4
    reqs = [{"index": "ux", "shard": 0, "window": 4,
             "body": {"query": {"match": {
                 "body": f"w{int(rng.integers(0, 30))}"}},
                 "aggs": {"brands": {"terms": {"field": "brand"}}}}}
            for _ in range(n)]
    expired_i = int(rng.integers(0, n))
    cancelled_i = int((expired_i + 1 + rng.integers(0, n - 1)) % n)
    reqs[expired_i]["budget_remaining"] = 0.0

    deferreds = [batcher.enqueue(dict(r)) for r in reqs]
    key = next(iter(batcher._queues))
    members = list(batcher._queues[key])
    assert len(members) == n
    assert members[0].spec.kind == "dense"
    members[cancelled_i].task.cancel("chaos cancel")

    results = [None] * n
    for i, d in enumerate(deferreds):
        d._subscribe(lambda v, i=i: results.__setitem__(i, ("ok", v)),
                     lambda e, i=i: results.__setitem__(i, ("err", e)))
    batcher._drain(key)
    assert all(r is not None for r in results)
    for i, (kind, payload) in enumerate(results):
        if i == expired_i:
            assert kind == "err" and "budget expired" in str(payload)
        elif i == cancelled_i:
            assert kind == "err" and "cancelled" in str(payload)
        else:
            assert kind == "ok", payload
            shard = sts.indices.shard("ux", 0)
            ref = sts.execute_query_member(
                dict(reqs[i]), shard.engine.acquire_reader())
            assert payload["docs"] == ref["docs"]
            assert payload["total"] == ref["total"]
            assert payload["aggs_partial"] == ref["aggs_partial"]


def test_cancelled_unique_does_not_poison_memo_duplicates(cluster,
                                                          monkeypatch):
    """Per-drain memo: the memoized unique's OWN death (cancellation
    mid-execution) must not reject its duplicates — the first duplicate
    re-executes under its own checks and is promoted as the memo source
    for the rest."""
    c = cluster
    sts = c.nodes["node0"].search_transport
    batcher = sts.batcher
    from elasticsearch_tpu.utils.errors import TaskCancelledError
    body = {"query": {"match": {"body": "w5 w6"}},
            "aggs": {"brands": {"terms": {"field": "brand"}}}}
    reqs = [{"index": "ux", "shard": 0, "window": 3,
             "body": json.loads(json.dumps(body))} for _ in range(3)]
    deferreds = [batcher.enqueue(dict(r)) for r in reqs]
    key = next(k for k, q in batcher._queues.items() if q)
    members = list(batcher._queues[key])
    assert members[0].spec.kind == "dense"

    orig = sts.execute_query_member
    calls = []

    def cancelled_first(req, reader, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise TaskCancelledError("chaos: unique cancelled")
        return orig(req, reader, **kw)
    monkeypatch.setattr(sts, "execute_query_member", cancelled_first)

    results = [None] * 3
    for i, d in enumerate(deferreds):
        d._subscribe(lambda v, i=i: results.__setitem__(i, ("ok", v)),
                     lambda e, i=i: results.__setitem__(i, ("err", e)))
    batcher._drain(key)
    kind0, payload0 = results[0]
    assert kind0 == "err" and "cancelled" in str(payload0)
    # one re-execution (the promoted duplicate) serves BOTH duplicates
    assert len(calls) == 2
    ref = orig(dict(reqs[1]),
               sts.indices.shard("ux", 0).engine.acquire_reader())
    for i in (1, 2):
        kind, payload = results[i]
        assert kind == "ok", payload
        assert payload["docs"] == ref["docs"]
        assert payload["total"] == ref["total"]
        assert payload["aggs_partial"] == ref["aggs_partial"]


def test_duplicate_cancelled_mid_drain_rejects(cluster, monkeypatch):
    """A memo DUPLICATE whose task is cancelled after drain entry (while
    its unique executes) rejects at fan-out instead of resolving with a
    result its caller abandoned; the unique is unaffected."""
    c = cluster
    sts = c.nodes["node0"].search_transport
    batcher = sts.batcher
    body = {"query": {"match": {"body": "w7"}},
            "aggs": {"p": {"avg": {"field": "price"}}}}
    reqs = [{"index": "ux", "shard": 0, "window": 3,
             "body": json.loads(json.dumps(body))} for _ in range(2)]
    deferreds = [batcher.enqueue(dict(r)) for r in reqs]
    key = next(k for k, q in batcher._queues.items() if q)
    members = list(batcher._queues[key])

    orig = sts.execute_query_member

    def cancel_duplicate(req, reader, **kw):
        members[1].task.cancel("chaos: duplicate abandoned")
        return orig(req, reader, **kw)
    monkeypatch.setattr(sts, "execute_query_member", cancel_duplicate)

    results = [None] * 2
    for i, d in enumerate(deferreds):
        d._subscribe(lambda v, i=i: results.__setitem__(i, ("ok", v)),
                     lambda e, i=i: results.__setitem__(i, ("err", e)))
    batcher._drain(key)
    assert results[0][0] == "ok"
    assert results[1][0] == "err"
    assert "cancelled" in str(results[1][1])


# ---------------------------------------------------------------------------
# the deleted dual path stays deleted
# ---------------------------------------------------------------------------

def test_deleted_solo_entry_points_stay_deleted():
    """git-grep-style guard: the solo kernel duplicates and the
    dual-path plumbing deleted by the unification must not reappear in
    the package source. One kernel call-site per query class."""
    root = Path(__file__).resolve().parent.parent / "elasticsearch_tpu"
    forbidden = [
        # the duplicated solo kernels
        re.compile(r"def _wand_topk_shard\b"),
        re.compile(r"def _plane_knn_winners_solo\b"),
        re.compile(r"def _ann_segment_topk\b"),
        # the dual-path plumbing
        re.compile(r"def _execute_query_solo\b"),
        re.compile(r"_execute_query_solo\("),
        re.compile(r"def try_enqueue\b"),
        re.compile(r"try_enqueue\("),
        re.compile(r"class _FallbackSolo\b"),
        re.compile(r"\b_FallbackSolo\b"),
    ]
    hits = []
    for path in sorted(root.rglob("*.py")):
        text = path.read_text()
        for pat in forbidden:
            if pat.search(text):
                hits.append((str(path.relative_to(root)), pat.pattern))
    assert not hits, f"deleted entry points resurfaced: {hits}"


# ---------------------------------------------------------------------------
# _tasks phase fidelity at occupancy 1
# ---------------------------------------------------------------------------

def test_tasks_phase_fidelity_occupancy_one(cluster, monkeypatch):
    """A (formerly solo) occupancy-1 member's shard task walks
    queued -> dispatch -> demux, not "query" for its whole life."""
    c = cluster
    batcher = c.nodes["node0"].search_transport.batcher
    seen = []
    orig = batcher._set_phase

    def spy(members, phase, occupancy=None, **kw):
        for m in members:
            if m.task is not None:
                seen.append(phase)
                break
        orig(members, phase, occupancy=occupancy, **kw)
    monkeypatch.setattr(batcher, "_set_phase", spy)

    for body, plane in (
            ({"query": {"match": {"body": "w1 w2"}}}, "batch"),  # text
            ({"query": {"match": {"body": "w1"}},                # dense
              "aggs": {"b": {"terms": {"field": "brand"}}}}, "dense")):
        seen.clear()
        req = {"index": "ux", "shard": 0, "window": 3, "body": body}
        deferred = batcher.enqueue(req)
        member = next(m for q in batcher._queues.values() for m in q)
        assert member.task.status == {"phase": "queued",
                                      "data_plane": plane}
        got = []
        deferred._subscribe(lambda v: got.append(("ok", v)),
                            lambda e: got.append(("err", e)))
        key = next(k for k, q in batcher._queues.items() if q)
        batcher._drain(key)
        assert got and got[0][0] == "ok"
        assert "dispatch" in seen and "demux" in seen, (body, seen)
        assert seen.index("dispatch") < seen.index("demux")


# ---------------------------------------------------------------------------
# request-cache intake consult + adaptive per-key max_size
# ---------------------------------------------------------------------------

def test_request_cache_hit_answers_at_intake(cluster):
    """A cacheable duplicate (size-0 count over an unchanged reader)
    answers at ``enqueue`` intake — no member, no collection-window
    wait — once a drain has filled the cache."""
    c = cluster
    batcher = c.nodes["node0"].search_transport.batcher
    req = {"index": "ux", "shard": 0, "window": 0,
           "body": {"query": {"match": {"body": "w3"}}}}
    first = batcher.enqueue(dict(req))
    assert not isinstance(first, dict)      # queued: a real member
    got = []
    first._subscribe(lambda v: got.append(v), lambda e: got.append(e))
    key = next(k for k, q in batcher._queues.items() if q)
    batcher._drain(key)
    assert got and isinstance(got[0], dict)

    before = batcher.stats["request_cache_intake_hits"]
    hit = batcher.enqueue(dict(req))
    assert isinstance(hit, dict)            # answered NOW, not queued
    assert batcher.stats["request_cache_intake_hits"] == before + 1
    assert hit["total"] == got[0]["total"]
    assert not any(batcher._queues.values())


def test_max_size_shrinks_on_breaker_trip_and_regrows(cluster,
                                                      monkeypatch):
    """A breaker trip mid-drain halves the key's effective drain cap
    (the next drains fit the budget); a successful drain at the shrunk
    cap regrows it toward the setting."""
    from elasticsearch_tpu.utils.errors import CircuitBreakingError
    c = cluster
    batcher = c.nodes["node0"].search_transport.batcher
    orig = batcher._execute
    state = {"tripped": False}

    def trip_once(key, live):
        if len(live) > 1 and not state["tripped"]:
            state["tripped"] = True
            raise CircuitBreakingError("injected HBM pressure")
        return orig(key, live)
    monkeypatch.setattr(batcher, "_execute", trip_once)

    before = dict(batcher.stats)

    def fill(n):
        reqs = [{"index": "ux", "shard": 0, "window": 6,
                 "body": {"query": {"match": {"body": f"w{i} w0"}}}}
                for i in range(n)]
        boxes = []
        for r in reqs:
            got = []
            d = batcher.enqueue(r)
            d._subscribe(lambda v, got=got: got.append(("ok", v)),
                         lambda e, got=got: got.append(("err", e)))
            boxes.append(got)
        for k in [k for k, q in batcher._queues.items() if q]:
            batcher._drain(k)
        return boxes

    boxes = fill(4)
    # the trip shed no queries: every member re-drained at occupancy 1
    assert all(b and b[0][0] == "ok" for b in boxes)
    assert state["tripped"]
    assert batcher.stats["max_size_shrinks"] == \
        before["max_size_shrinks"] + 1
    assert batcher.stats["member_redrains"] >= \
        before["member_redrains"] + 4
    key = next(k for k in batcher._key_state
               if k[:2] == ("ux", 0) and k[2] == "text" and k[4] == 6)
    assert batcher._key_state[key]["max_size"] == 2

    # a full drain at the shrunk cap proves headroom: the cap regrows
    boxes = fill(2)
    assert all(b and b[0][0] == "ok" for b in boxes)
    assert batcher.stats["max_size_grows"] == before["max_size_grows"] + 1
    assert (batcher._key_state[key]["max_size"] or
            batcher.max_size()) > 2


# ---------------------------------------------------------------------------
# slow sweep
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_unified_shapes_sweep_slow(cluster):
    """>=5-seed sweep of the newly-batched-shapes golden parity."""
    for k in range(max(CHAOS_SEEDS, 5)):
        c = cluster
        client = c.client()
        rng = np.random.default_rng(7 + 991 * (k + 1))
        shapes = _shape_bodies(rng)
        solo = {n: _strip(_ok(*c.call(
            lambda cb, b=b: client.search(
                "ux", json.loads(json.dumps(b)), cb))))
            for n, b in shapes.items()}
        wave = _wave(c, list(shapes.values()))
        for name, resp in zip(list(shapes), wave):
            assert _strip(resp) == solo[name], (k, name)
