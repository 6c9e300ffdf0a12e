"""Mesh-sharded device planes: golden parity + degradation + lifecycle.

The mesh-sharded SPMD path (ops/device_segment.py MeshPlaneRegistry +
search/plane_exec.py mesh executors + search/mesh_executor.py) must be
invisible in results: a co-located fan-out served from the mesh returns
byte-identical responses to the per-shard RPC scatter-gather for every
query class (bm25 / exact kNN / filtered kNN / sparse, totals tracked,
clipped and disabled, deletes included), a mesh miss (HBM budget,
IVF-routed shards, disabled setting) degrades to the unchanged fan-out,
refresh publishes incrementally, and the single-device mesh layout is
the byte-identity baseline against the per-shard plane executors.
"""

import copy
import json
import os

import numpy as np
import pytest

from elasticsearch_tpu.index import InternalEngine
from elasticsearch_tpu.indices.breaker import BREAKERS
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.ops.device_segment import MESH_PLANES, PLANES
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.batch_executor import (
    BatchSpec, _build_ctxs, _knn_demux, batched_knn_shard,
    batched_sparse_shard, batched_wand_topk_shard,
)
from elasticsearch_tpu.search.plane_exec import (
    MeshFallback, mesh_knn_winners, mesh_sparse_topk, mesh_wand_topk,
)

CHAOS_SEEDS = int(os.environ.get("CHAOS_SEEDS", "1") or "1")

pytestmark = pytest.mark.mesh


@pytest.fixture(autouse=True)
def _mesh_defaults():
    """Every test starts from default mesh/plane config and empty
    registries (both are process-global, like the breaker service)."""
    for reg in (MESH_PLANES, PLANES):
        reg.clear()
    MESH_PLANES.enabled = True
    MESH_PLANES.min_shards = 2
    MESH_PLANES.dp = 1
    MESH_PLANES.max_devices = 0
    MESH_PLANES.hosts = None
    PLANES.enabled = True
    PLANES.min_segments = 2
    yield
    for reg in (MESH_PLANES, PLANES):
        reg.clear()
    MESH_PLANES.enabled = True
    MESH_PLANES.min_shards = 2
    MESH_PLANES.dp = 1
    MESH_PLANES.max_devices = 0
    MESH_PLANES.hosts = None
    PLANES.enabled = True


def _engine(seed: int, n_docs: int = 90, cuts=(30, 60), ivf: bool = False):
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(30)]
    vec_mapping = {"type": "dense_vector", "dims": 8,
                   "similarity": "cosine"}
    if ivf:
        vec_mapping["index_options"] = {"type": "ivf", "nlist": 4,
                                        "nprobe": 4}
    eng = InternalEngine(
        MapperService({"properties": {
            "body": {"type": "text"},
            "vec": vec_mapping,
            "feats": {"type": "rank_features"},
            "tag": {"type": "keyword"}}}),
        shard_label=f"me{seed}{'i' if ivf else ''}")
    for i in range(n_docs):
        eng.index(str(i), {
            "body": " ".join(rng.choice(
                vocab, size=int(rng.integers(4, 14)))),
            "vec": [float(x) for x in rng.standard_normal(8)],
            "feats": {f"f{j}": float(rng.random() + 0.1)
                      for j in rng.integers(0, 12, 3)},
            "tag": f"t{i % 3}"})
        if i in cuts:
            eng.refresh()
    for i in range(0, n_docs, 13):     # deletes included, per the issue
        eng.delete(str(i))
    eng.refresh()
    return eng, rng


def _shards(seed: int, n_shards: int = 3, ivf: bool = False):
    engines = [
        _engine(seed + 100 * s, ivf=ivf)[0] for s in range(n_shards)]
    readers = [e.acquire_reader() for e in engines]
    shard_segments = [(("idx", sid), list(r.segments))
                      for sid, r in enumerate(readers)]
    return engines, readers, shard_segments


def _ctxs(reader, mappers, query=None):
    dfs = None
    if query is not None:
        from elasticsearch_tpu.search.phase import shard_term_stats
        _dc, dfs = shard_term_stats(reader, mappers, query)
    return _build_ctxs(reader, mappers,
                       sum(s.n_docs for s in reader.segments), dfs)


def _assert_rows_same(mine, ref, scores_exact=False):
    """(candidates, total, relation, max_score, prune) tuples equal."""
    assert [(c.segment_idx, c.doc) for c in mine[0]] == \
        [(c.segment_idx, c.doc) for c in ref[0]]
    a = [c.score for c in mine[0]]
    b = [c.score for c in ref[0]]
    if scores_exact:
        np.testing.assert_array_equal(a, b)
    else:
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    assert mine[1] == ref[1] and mine[2] == ref[2]
    if len(mine) > 4 and mine[4] is not None:
        assert mine[4] == ref[4]     # prune accounting


# ---------------------------------------------------------------------------
# golden parity: mesh executors vs the served per-shard batch path
# ---------------------------------------------------------------------------

def _golden_all_classes(seed: int, scores_exact: bool = False):
    engines, readers, shard_segments = _shards(seed)
    mappers = engines[0].mappers
    rng = np.random.default_rng(seed)

    # text — totals tracked (default), clipped, and DISABLED
    q = dsl.parse_query({"match": {"body": "w1 w3 w7"}})
    clauses = [[("w1 w3 w7", 1.0)], [("w2 w9", 1.0)]]
    shard_ctxs = [_ctxs(r, mappers, q) for r in readers]
    mpart = MESH_PLANES.get(shard_segments, "postings", "body")
    assert mpart is not None
    for track in (10_000, 5, 0):
        got = mesh_wand_topk(shard_ctxs, mpart, "body", clauses, 10,
                             track)
        assert got is not None
        for si, r in enumerate(readers):
            ref = batched_wand_topk_shard(
                _ctxs(r, mappers, q), "body", clauses, 10, track)
            for qi in range(len(clauses)):
                _assert_rows_same(got[si][qi], ref[qi],
                                  scores_exact=scores_exact)

    # kNN — unfiltered + filtered (distinct and shared filters)
    filt = dsl.parse_query({"term": {"tag": "t1"}})
    specs = [
        BatchSpec(kind="knn", field="vec", window=10, k=7,
                  num_candidates=100, boost=1.0,
                  query_vector=[float(x)
                                for x in rng.standard_normal(8)]),
        BatchSpec(kind="knn", field="vec", window=10, k=7,
                  num_candidates=100, boost=1.0,
                  query_vector=[float(x)
                                for x in rng.standard_normal(8)],
                  filter=filt, filter_key=repr(filt)),
    ]
    shard_ctxs = [_ctxs(r, mappers) for r in readers]
    mpart_v = MESH_PLANES.get(shard_segments, "vectors", "vec")
    assert mpart_v is not None
    raw = mesh_knn_winners(shard_ctxs, mpart_v, "vec", specs, 7)
    for si, r in enumerate(readers):
        ref = batched_knn_shard(_ctxs(r, mappers), "vec", specs, 7)
        mine = _knn_demux(specs, raw[si], 7)
        for qi in range(len(specs)):
            _assert_rows_same(mine[qi], ref[qi],
                              scores_exact=scores_exact)

    # sparse
    toks = {"f1": 1.2, "f4": 0.7, "f9": 0.4}
    spec_s = BatchSpec(kind="sparse", field="feats", window=10,
                       tokens=toks, boost=1.0)
    expansions = [[(t, w) for t, w in toks.items()]]
    mpart_f = MESH_PLANES.get(shard_segments, "features", "feats")
    assert mpart_f is not None
    raw = mesh_sparse_topk(shard_ctxs, mpart_f, "feats", expansions, 10)
    for si, r in enumerate(readers):
        ref = batched_sparse_shard(_ctxs(r, mappers), "feats", [spec_s],
                                   10)
        cands, total, max_score = raw[si][0]
        assert [(c.segment_idx, c.doc) for c in cands] == \
            [(c.segment_idx, c.doc) for c in ref[0][0]]
        assert total == ref[0][1]


@pytest.mark.parametrize("seed", [41 + 997 * k for k in range(CHAOS_SEEDS)])
def test_golden_mesh_vs_per_shard(seed):
    _golden_all_classes(seed)


def test_single_device_mesh_byte_identity():
    """The 1-device mesh layout is the byte-identity baseline: every
    slot's kernel body is the single-shard plane kernel, so scores must
    be EXACTLY equal (not just allclose) to the per-shard path."""
    MESH_PLANES.max_devices = 1
    _golden_all_classes(17, scores_exact=True)
    from elasticsearch_tpu.parallel.mesh import mesh_layout
    mesh, n_slots, _ = mesh_layout(3, dp=1, max_devices=1)
    assert int(mesh.shape["shard"]) == 1 and n_slots == 3


@pytest.mark.slow
@pytest.mark.parametrize("seed",
                         [71 + 613 * k for k in range(max(CHAOS_SEEDS, 5))])
def test_golden_mesh_sweep_slow(seed):
    _golden_all_classes(seed)


def test_dp_axis_golden_parity():
    """search.mesh.dp > 1: the query stack splits over the dp mesh axis
    (kNN) / rides replicated (text) — results identical either way."""
    MESH_PLANES.dp = 2
    engines, readers, shard_segments = _shards(77)
    mappers = engines[0].mappers
    rng = np.random.default_rng(5)
    specs = [BatchSpec(kind="knn", field="vec", window=10, k=7,
                       num_candidates=100, boost=1.0,
                       query_vector=[float(x)
                                     for x in rng.standard_normal(8)])
             for _ in range(3)]
    shard_ctxs = [_ctxs(r, mappers) for r in readers]
    mv = MESH_PLANES.get(shard_segments, "vectors", "vec")
    assert mv is not None and int(mv.mesh.shape["dp"]) == 2
    raw = mesh_knn_winners(shard_ctxs, mv, "vec", specs, 7)
    for si, r in enumerate(readers):
        ref = batched_knn_shard(_ctxs(r, mappers), "vec", specs, 7)
        mine = _knn_demux(specs, raw[si], 7)
        for qi in range(3):
            _assert_rows_same(mine[qi], ref[qi])
    q = dsl.parse_query({"match": {"body": "w1 w3"}})
    text_ctxs = [_ctxs(r, mappers, q) for r in readers]
    mp = MESH_PLANES.get(shard_segments, "postings", "body")
    got = mesh_wand_topk(text_ctxs, mp, "body", [[("w1 w3", 1.0)]], 10,
                         10_000)
    for si, r in enumerate(readers):
        ref = batched_wand_topk_shard(_ctxs(r, mappers, q), "body",
                                      [[("w1 w3", 1.0)]], 10, 10_000)
        _assert_rows_same(got[si][0], ref[0])


def test_dp_axis_query_split_text_sparse_parity():
    """search.mesh.dp > 1 splits the TEXT and SPARSE flat query stacks
    over the dp axis too (each row scores its own contiguous slice of
    the micro-batch, the kNN rule) — including a query count that pads
    unevenly into the rows. Results identical to the per-shard path."""
    MESH_PLANES.dp = 2
    engines, readers, shard_segments = _shards(83)
    mappers = engines[0].mappers

    q = dsl.parse_query({"match": {"body": "w1 w3 w7 w2 w9 w5"}})
    clauses = [[("w1 w3 w7", 1.0)], [("w2 w9", 1.0)], [("w5", 1.0)]]
    text_ctxs = [_ctxs(r, mappers, q) for r in readers]
    mp = MESH_PLANES.get(shard_segments, "postings", "body")
    assert mp is not None and int(mp.mesh.shape["dp"]) == 2
    for track in (10_000, 0):
        got = mesh_wand_topk(text_ctxs, mp, "body", clauses, 10, track)
        for si, r in enumerate(readers):
            ref = batched_wand_topk_shard(
                _ctxs(r, mappers, q), "body", clauses, 10, track)
            for qi in range(len(clauses)):
                _assert_rows_same(got[si][qi], ref[qi])

    tok_sets = [{"f1": 1.2, "f4": 0.7}, {"f2": 0.9, "f9": 0.4},
                {"f5": 1.0}]
    specs = [BatchSpec(kind="sparse", field="feats", window=10,
                       tokens=t, boost=1.0) for t in tok_sets]
    expansions = [[(t, w) for t, w in toks.items()] for toks in tok_sets]
    shard_ctxs = [_ctxs(r, mappers) for r in readers]
    mf = MESH_PLANES.get(shard_segments, "features", "feats")
    assert mf is not None and int(mf.mesh.shape["dp"]) == 2
    raw = mesh_sparse_topk(shard_ctxs, mf, "feats", expansions, 10)
    for si, r in enumerate(readers):
        ref = batched_sparse_shard(_ctxs(r, mappers), "feats", specs, 10)
        for qi in range(len(specs)):
            cands, total, _mx = raw[si][qi]
            assert [(c.segment_idx, c.doc) for c in cands] == \
                [(c.segment_idx, c.doc) for c in ref[qi][0]]
            assert total == ref[qi][1]


def test_host_capped_layout_golden_parity():
    """A declared host topology caps the mesh at the fleet's devices and
    makes the device order host-contiguous — a 2x2 virtual fleet (4 of
    the 8 test devices) must stay result-identical for every class."""
    from elasticsearch_tpu.parallel.mesh import (
        mesh_layout, parse_host_topology,
    )
    topo = parse_host_topology("2x2")
    MESH_PLANES.hosts = topo
    _golden_all_classes(53)
    mesh, _n_slots, _ = mesh_layout(3, dp=1, hosts=topo)
    assert int(mesh.shape["shard"]) <= topo.n_devices


def test_mesh_ivf_shard_falls_back():
    """IVF-routed shards keep the per-shard fan-out (whose probe path
    serves them): the mesh executor must refuse, not approximate."""
    engines, readers, shard_segments = _shards(23, n_shards=2, ivf=True)
    mappers = engines[0].mappers
    shard_ctxs = [_ctxs(r, mappers) for r in readers]
    mpart = MESH_PLANES.get(shard_segments, "vectors", "vec")
    assert mpart is not None
    spec = BatchSpec(kind="knn", field="vec", window=10, k=5,
                     num_candidates=16, boost=1.0,
                     query_vector=[0.1] * 8)
    with pytest.raises(MeshFallback):
        mesh_knn_winners(shard_ctxs, mpart, "vec", [spec], 5)


def test_refresh_during_mesh_query_incremental():
    """A refresh on one member shard re-packs the mesh plane
    incrementally (publish listeners) while a point-in-time reader from
    before the refresh still queries its own generation's part."""
    engines, readers, shard_segments = _shards(31, n_shards=2)
    mappers = engines[0].mappers
    q = dsl.parse_query({"match": {"body": "w1 w3"}})
    clauses = [[("w1 w3", 1.0)]]
    shard_ctxs = [_ctxs(r, mappers, q) for r in readers]
    mpart = MESH_PLANES.get(shard_segments, "postings", "body")
    assert mpart is not None
    before = mesh_wand_topk(shard_ctxs, mpart, "body", clauses, 10,
                            10_000)

    # append-only refresh on shard 0 (new segment), publish eagerly
    rng = np.random.default_rng(9)
    for i in range(300, 330):
        engines[0].index(str(i), {
            "body": "w1 w3 w3",
            "vec": [float(x) for x in rng.standard_normal(8)],
            "feats": {"f1": 1.0}, "tag": "t0"})
    engines[0].refresh()
    MESH_PLANES.on_refresh(("idx", 0), engines[0].segments)
    assert MESH_PLANES.stats["mesh_plane_incremental_appends"] >= 1

    # the PIT readers' part still serves the old snapshot identically
    again = mesh_wand_topk(shard_ctxs, mpart, "body", clauses, 10,
                           10_000)
    for si in range(2):
        _assert_rows_same(again[si][0], before[si][0],
                          scores_exact=True)

    # new readers see the appended docs through the new generation
    new_readers = [e.acquire_reader() for e in engines]
    new_segments = [(("idx", sid), list(r.segments))
                    for sid, r in enumerate(new_readers)]
    new_ctxs = [_ctxs(r, mappers, q) for r in new_readers]
    mpart2 = MESH_PLANES.get(new_segments, "postings", "body")
    assert mpart2 is not None and mpart2 is not mpart
    after = mesh_wand_topk(new_ctxs, mpart2, "body", clauses, 10,
                           10_000)
    assert after[0][0][1] > before[0][0][1]   # shard 0 grew matches


# ---------------------------------------------------------------------------
# served path: e2e parity + fallback + stats through the node layer
# ---------------------------------------------------------------------------

def _e2e_bodies(rng):
    return [
        {"query": {"match": {"body": "w1 w3 w7"}}, "size": 8},
        {"query": {"match": {"body": "w2 w4"}}, "size": 5,
         "track_total_hits": False},
        {"query": {"match": {"body": "w2 w4"}}, "size": 5,
         "track_total_hits": 7},
        {"query": {"knn": {"field": "vec", "k": 6, "query_vector":
                           [float(x) for x in rng.standard_normal(8)]}},
         "size": 6},
        {"query": {"knn": {"field": "vec", "k": 6, "query_vector":
                           [float(x) for x in rng.standard_normal(8)],
                           "filter": {"term": {"tag": "t1"}}}},
         "size": 6},
        {"query": {"text_expansion": {"feats": {"tokens":
                                                {"f1": 1.2, "f4": 0.7}}}},
         "size": 7},
    ]


def _e2e_cluster(seed: int):
    from elasticsearch_tpu.testing import InProcessCluster
    cluster = InProcessCluster(n_nodes=1, seed=seed)
    cluster.start()
    client = cluster.client()
    cluster.call(lambda cb: client.create_index(
        "m", {"settings": {"number_of_shards": 3,
                           "number_of_replicas": 0},
              "mappings": {"properties": {
                  "body": {"type": "text"},
                  "vec": {"type": "dense_vector", "dims": 8,
                          "similarity": "cosine"},
                  "feats": {"type": "rank_features"},
                  "tag": {"type": "keyword"}}}}, cb))
    cluster.ensure_green("m")
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(30)]
    for d in range(120):
        cluster.call(lambda cb, d=d: client.index_doc(
            "m", f"d{d}", {
                "body": " ".join(rng.choice(
                    vocab, size=int(rng.integers(4, 12)))),
                "vec": [float(x) for x in rng.standard_normal(8)],
                "feats": {f"f{j}": float(rng.random() + 0.1)
                          for j in rng.integers(0, 12, 3)},
                "tag": f"t{d % 3}"}, cb))
    for d in range(0, 120, 17):
        cluster.call(lambda cb, d=d: client.delete_doc("m", f"d{d}", cb))
    cluster.call(lambda cb: client.refresh("m", cb))
    # backend first-init on the RPC path (the mesh never pays first-init)
    cluster.call(lambda cb: client.search(
        "m", {"query": {"match": {"body": "w0"}}, "size": 1}, cb))
    return cluster, client, rng


@pytest.mark.parametrize("seed", [3 + 577 * k for k in range(CHAOS_SEEDS)])
def test_e2e_mesh_vs_fanout_byte_parity(seed):
    cluster, client, rng = _e2e_cluster(seed)
    try:
        bodies = _e2e_bodies(rng)
        mesh_resps = []
        for body in bodies:
            resp, err = cluster.call(
                lambda cb, b=body: client.search("m", copy.deepcopy(b),
                                                 cb))
            assert err is None, (body, err)
            assert resp.get("_data_plane") == "mesh_plane", \
                (body, resp.get("_data_plane"))
            mesh_resps.append(resp)
        cluster.call(lambda cb: client.cluster_update_settings(
            {"persistent": {"search.mesh.enabled": False}}, cb))
        for body, mesh_resp in zip(bodies, mesh_resps):
            resp, err = cluster.call(
                lambda cb, b=body: client.search("m", copy.deepcopy(b),
                                                 cb))
            assert err is None, (body, err)
            assert resp.get("_data_plane") == "batch"
            a = {k: v for k, v in mesh_resp.items()
                 if k not in ("took", "_data_plane")}
            b = {k: v for k, v in resp.items() if k not in ("took", "_data_plane")}
            assert json.dumps(a, sort_keys=True) == \
                json.dumps(b, sort_keys=True), body
        node = next(iter(cluster.nodes.values()))
        stats = node.local_node_stats()["mesh_plane"]
        assert stats["mesh_searches"] >= len(bodies)
        assert stats["mesh_plane_builds"] >= 1
        assert stats["device_dispatches"] >= 1
    finally:
        cluster.stop()


def test_mesh_drain_memo_dedups_identical_members():
    """Identical same-tick mesh members pay ONE term-stats pass and one
    query-stack row (the shard batcher's per-drain memo discipline);
    every duplicate still gets its own pinned contexts and a response
    identical to a distinct member's."""
    cluster, client, rng = _e2e_cluster(41)
    try:
        node = next(iter(cluster.nodes.values()))
        ex = node.search_transport.mesh_executor
        body = {"query": {"match": {"body": "w1 w3"}}, "size": 6}
        boxes = []
        for _ in range(4):
            box = []
            client.search("m", copy.deepcopy(body),
                          lambda resp, err=None, box=box: box.append(
                              (resp, err)))
            boxes.append(box)
        cluster.run_until(lambda: all(boxes), 120.0)
        resps = []
        for box in boxes:
            resp, err = box[0]
            assert err is None, err
            assert resp.get("_data_plane") == "mesh_plane"
            resps.append(resp)
        # 4 identical members in one drain -> 1 execution + 3 memo hits
        assert ex.stats["memo_hits"] >= 3
        ref = {k: v for k, v in resps[0].items() if k not in ("took", "_data_plane")}
        for resp in resps[1:]:
            got = {k: v for k, v in resp.items() if k not in ("took", "_data_plane")}
            assert json.dumps(got, sort_keys=True) == \
                json.dumps(ref, sort_keys=True)
        # a duplicate's hits match a fresh solo mesh search exactly
        solo, err = cluster.call(
            lambda cb: client.search("m", copy.deepcopy(body), cb))
        assert err is None, err
        assert solo["hits"] == resps[0]["hits"]
    finally:
        cluster.stop()


def test_mesh_budget_refusal_counts_and_serves_none():
    """An over-budget mesh plane is refused AT ADMISSION (charged before
    upload), memoized, and reported as a miss — callers then keep the
    per-shard fan-out."""
    engines, readers, shard_segments = _shards(13, n_shards=2)
    old_limit = BREAKERS.breaker("device").limit
    try:
        BREAKERS.configure(device=1)
        assert MESH_PLANES.get(shard_segments, "postings", "body") is None
        misses = MESH_PLANES.stats["mesh_plane_miss_fallbacks"]
        assert misses >= 1
        # the refusal is memoized under the budget token: no re-pack
        assert MESH_PLANES.get(shard_segments, "postings", "body") is None
        assert MESH_PLANES.stats["mesh_plane_miss_fallbacks"] > misses
    finally:
        BREAKERS.configure(device=old_limit)
    # budget restored: the same key builds
    assert MESH_PLANES.get(shard_segments, "postings", "body") is not None


def test_e2e_mesh_miss_fallback_identity(monkeypatch):
    """A drain-time mesh miss (plane refused/evicted between submit and
    drain) degrades to the per-shard fan-out with identical results —
    never an error, never a wrong hit."""
    cluster, client, rng = _e2e_cluster(11)
    try:
        body = {"query": {"match": {"body": "w1 w3"}}, "size": 8}
        resp, err = cluster.call(
            lambda cb: client.search("m", copy.deepcopy(body), cb))
        assert err is None and resp.get("_data_plane") == "mesh_plane"

        monkeypatch.setattr(MESH_PLANES, "get",
                            lambda *a, **kw: None)
        resp2, err = cluster.call(
            lambda cb: client.search("m", copy.deepcopy(body), cb))
        assert err is None
        assert resp2.get("_data_plane") == "batch"
        a = {k: v for k, v in resp.items()
             if k not in ("took", "_data_plane")}
        b = {k: v for k, v in resp2.items() if k not in ("took", "_data_plane")}
        assert json.dumps(a, sort_keys=True) == \
            json.dumps(b, sort_keys=True)
        node = next(iter(cluster.nodes.values()))
        assert node.search_transport.mesh_executor.stats[
            "mesh_fallbacks"] >= 1
    finally:
        cluster.stop()


def test_mesh_can_match_skipped_parity():
    """The mesh path runs AFTER can-match: a fan-out where can-match
    skips a shard reports the same _shards.skipped as the RPC path and
    only scores the survivors on the mesh."""
    cluster, client, rng = _e2e_cluster(41)
    try:
        from elasticsearch_tpu.utils.murmur3 import shard_id_for
        # route a unique term onto shards 0 and 1 only — can-match skips
        # shard 2, and the two survivors keep the fan-out mesh-eligible
        picked = {}
        i = 0
        while set(picked) != {0, 1}:
            sid = shard_id_for(f"u{i}", 3)
            if sid in (0, 1) and sid not in picked:
                picked[sid] = f"u{i}"
            i += 1
        for sid, did in sorted(picked.items()):
            cluster.call(lambda cb, did=did: client.index_doc(
                "m", did, {"body": "zzyzx w1"}, cb))
        cluster.call(lambda cb: client.refresh("m", cb))
        body = {"query": {"match": {"body": "zzyzx"}}, "size": 5}
        resp, err = cluster.call(
            lambda cb: client.search("m", copy.deepcopy(body), cb))
        assert err is None
        assert resp.get("_data_plane") == "mesh_plane"
        assert resp["_shards"]["total"] == 3
        assert resp["_shards"]["skipped"] == 1
        assert len(resp["hits"]["hits"]) == 2
        cluster.call(lambda cb: client.cluster_update_settings(
            {"persistent": {"search.mesh.enabled": False}}, cb))
        ref, err = cluster.call(
            lambda cb: client.search("m", copy.deepcopy(body), cb))
        assert err is None and ref.get("_data_plane") == "batch"
        a = {k: v for k, v in resp.items()
             if k not in ("took", "_data_plane")}
        b = {k: v for k, v in ref.items() if k not in ("took", "_data_plane")}
        assert json.dumps(a, sort_keys=True) == \
            json.dumps(b, sort_keys=True)
    finally:
        cluster.stop()


def test_mesh_requires_active_local_copy():
    """Co-location means an ACTIVE local copy: a target whose routing
    copies exclude this node (e.g. only an initializing local replica
    exists) is not mesh-eligible, even if a shard instance is locally
    registered."""
    cluster, client, rng = _e2e_cluster(43)
    try:
        node = next(iter(cluster.nodes.values()))
        ex = node.search_transport.mesh_executor
        targets = [{"index": "m", "shard": s, "node": node.node_id,
                    "copies": [node.node_id]} for s in range(3)]
        body = {"query": {"match": {"body": "w1"}}, "size": 5}
        assert ex.try_submit("m", targets, body, 5, None,
                             lambda results: None)
        # same fan-out, but shard 1's active copy lives elsewhere
        targets[1]["copies"] = ["other-node"]
        assert not ex.try_submit("m", targets, body, 5, None,
                                 lambda results: None)
    finally:
        cluster.stop()


def test_cat_health_routes_through_master(monkeypatch):
    """Satellite: _cat/health and _cat/indices answer through the same
    master-routed async path _cluster/health uses (flagged local
    fallback included), not the local sync view."""
    from elasticsearch_tpu.rest.controller import RestRequest
    from elasticsearch_tpu.rest.routes import build_controller
    from elasticsearch_tpu.testing import InProcessCluster
    cluster = InProcessCluster(n_nodes=2, seed=7)
    cluster.start()
    try:
        client = cluster.client()
        cluster.call(lambda cb: client.create_index(
            "c", {"settings": {"number_of_shards": 1,
                               "number_of_replicas": 0}}, cb))
        cluster.ensure_green("c")
        # drive the cat routes on a NON-master node's controller
        state = next(iter(cluster.nodes.values()))._applied_state()
        non_master = next(n for n in cluster.nodes.values()
                          if n.node_id != state.master_node_id)
        routed = {"n": 0, "bulk": 0}
        orig = type(non_master.client).cluster_health_async
        orig_bulk = type(non_master.client).cluster_healths_async

        def spy(self, index, on_done):
            routed["n"] += 1
            return orig(self, index, on_done)

        def spy_bulk(self, indices, on_done):
            routed["n"] += 1
            routed["bulk"] += 1
            return orig_bulk(self, indices, on_done)
        monkeypatch.setattr(type(non_master.client),
                            "cluster_health_async", spy)
        monkeypatch.setattr(type(non_master.client),
                            "cluster_healths_async", spy_bulk)
        controller = build_controller(non_master.client)

        def do(path):
            out = []
            controller.dispatch(
                RestRequest(method="GET", path=path, query={},
                            body=None, raw_body=b""),
                lambda s, b: out.append((s, b)))
            cluster.run_until(lambda: bool(out), 60.0)
            return out[0]

        status, body = do("/_cat/health")
        assert status == 200 and "green" in str(body)
        status, body = do("/_cat/indices")
        assert status == 200 and "c" in str(body)
        status, body = do("/_cluster/stats")
        assert status == 200 and body["status"] in ("green", "yellow")
        assert routed["n"] >= 3
        # _cat/indices resolves every index's status in ONE bulk master
        # request, not one chained RPC per index
        assert routed["bulk"] == 1
    finally:
        cluster.stop()


# ---------------------------------------------------------------------------
# multi-host mesh: host-partitioned virtual fleet through the node layer
# ---------------------------------------------------------------------------

def _multihost_cluster(seed: int, n_nodes: int = 2, hosts_spec: str = "2",
                       replicas: int = 0):
    """`_e2e_cluster` grown to a virtual fleet: ``n_nodes`` cluster nodes
    partitioned onto ``hosts_spec`` virtual hosts (testing.py
    VirtualHostBackend), shards spread across them. The topology is
    DECLARED (cluster setting) only after the priming RPC search — the
    mesh never pays backend first-init, so ``search.mesh.hosts`` parses
    against an already-initialized device layer."""
    from elasticsearch_tpu.testing import InProcessCluster
    cluster = InProcessCluster(n_nodes=n_nodes, seed=seed,
                               mesh_hosts=hosts_spec)
    cluster.start()
    client = cluster.client("node0")
    cluster.call(lambda cb: client.create_index(
        "m", {"settings": {"number_of_shards": 3,
                           "number_of_replicas": replicas},
              "mappings": {"properties": {
                  "body": {"type": "text"},
                  "vec": {"type": "dense_vector", "dims": 8,
                          "similarity": "cosine"},
                  "feats": {"type": "rank_features"},
                  "tag": {"type": "keyword"}}}}, cb))
    cluster.ensure_green("m")
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(30)]
    for d in range(120):
        cluster.call(lambda cb, d=d: client.index_doc(
            "m", f"d{d}", {
                "body": " ".join(rng.choice(
                    vocab, size=int(rng.integers(4, 12)))),
                "vec": [float(x) for x in rng.standard_normal(8)],
                "feats": {f"f{j}": float(rng.random() + 0.1)
                          for j in rng.integers(0, 12, 3)},
                "tag": f"t{d % 3}"}, cb))
    for d in range(0, 120, 17):
        cluster.call(lambda cb, d=d: client.delete_doc("m", f"d{d}", cb))
    cluster.call(lambda cb: client.refresh("m", cb))
    cluster.call(lambda cb: client.search(
        "m", {"query": {"match": {"body": "w0"}}, "size": 1}, cb))
    cluster.call(lambda cb: client.cluster_update_settings(
        {"persistent": {"search.mesh.hosts": hosts_spec}}, cb))
    return cluster, client, rng


@pytest.mark.parametrize("seed", [5 + 389 * k for k in range(CHAOS_SEEDS)])
def test_e2e_multihost_mesh_vs_fanout_byte_parity(seed):
    """Targets spanning mesh-member HOSTS serve through ONE mesh program
    per phase with responses byte-identical to the cross-node RPC
    fan-out — deletes, filtered kNN, and every track_total_hits mode
    included — and the per-host serving counters show work landing on
    BOTH virtual hosts. Zero untyped fallbacks throughout."""
    from elasticsearch_tpu.search.telemetry import TELEMETRY
    cluster, client, rng = _multihost_cluster(seed)
    try:
        before_unknown = TELEMETRY.fallbacks.get("unknown", 0)
        bodies = _e2e_bodies(rng)
        mesh_resps = []
        for body in bodies:
            resp, err = cluster.call(
                lambda cb, b=body: client.search("m", copy.deepcopy(b),
                                                 cb))
            assert err is None, (body, err)
            assert resp.get("_data_plane") == "mesh_plane", \
                (body, resp.get("_data_plane"))
            mesh_resps.append(resp)
        ex = cluster.nodes["node0"].search_transport.mesh_executor
        hot = {h for h, c in ex.per_host_stats.items()
               if c.get("shard_results", 0) > 0}
        assert len(hot) >= 2, ex.per_host_stats
        stats = cluster.nodes["node0"].local_node_stats()["mesh_plane"]
        assert stats["hosts"]["n_hosts"] == 2, stats.get("hosts")
        assert stats.get("per_host"), stats
        cluster.call(lambda cb: client.cluster_update_settings(
            {"persistent": {"search.mesh.enabled": False}}, cb))
        for body, mesh_resp in zip(bodies, mesh_resps):
            resp, err = cluster.call(
                lambda cb, b=body: client.search("m", copy.deepcopy(b),
                                                 cb))
            assert err is None, (body, err)
            assert resp.get("_data_plane") == "batch"
            a = {k: v for k, v in mesh_resp.items()
                 if k not in ("took", "_data_plane")}
            b = {k: v for k, v in resp.items() if k not in ("took", "_data_plane")}
            assert json.dumps(a, sort_keys=True) == \
                json.dumps(b, sort_keys=True), body
        assert TELEMETRY.fallbacks.get("unknown", 0) == before_unknown
    finally:
        cluster.stop()


def test_multihost_host_loss_typed_fallback():
    """A mesh-member host dropping mid-query degrades through the TYPED
    mesh_host_lost fallback to the RPC path, whose reroute machinery
    finds the surviving replica — identical results, zero untyped
    ("unknown") fallbacks, never an error."""
    from elasticsearch_tpu.search.telemetry import TELEMETRY
    cluster, client, rng = _multihost_cluster(
        29, n_nodes=3, hosts_spec="3x2", replicas=1)
    try:
        coord = cluster.nodes["node0"]
        ex = coord.search_transport.mesh_executor
        body = {"query": {"match": {"body": "w1 w3"}}, "size": 8}
        resp, err = cluster.call(
            lambda cb: client.search("m", copy.deepcopy(body), cb))
        assert err is None and resp.get("_data_plane") == "mesh_plane"
        remote_hot = {h for h, c in ex.per_host_stats.items()
                      if h != "host_0" and c.get("shard_results", 0) > 0}
        assert remote_hot, ex.per_host_stats

        before = dict(TELEMETRY.fallbacks)
        orig_execute = ex._execute

        def sabotage(key, members):
            remote = sorted({n for n in members[0].serving.values()
                             if n != coord.node_id})
            assert remote, "expected a remote-served shard"
            for n in remote:
                cluster.crash_node(n)
            return orig_execute(key, members)
        ex._execute = sabotage
        try:
            body2 = {"query": {"match": {"body": "w2 w5"}}, "size": 8}
            resp2, err = cluster.call(
                lambda cb: client.search("m", copy.deepcopy(body2), cb),
                max_time=180.0)
        finally:
            ex._execute = orig_execute
        assert err is None, err
        assert resp2.get("_data_plane") == "batch"
        lost = TELEMETRY.fallbacks.get("mesh_host_lost", 0) - \
            before.get("mesh_host_lost", 0)
        assert lost >= 1, TELEMETRY.fallbacks
        assert TELEMETRY.fallbacks.get("unknown", 0) == \
            before.get("unknown", 0)
        host_losses = sum(c.get("host_losses", 0)
                          for c in ex.per_host_stats.values())
        assert host_losses >= 1, ex.per_host_stats
        # identical results off the surviving replicas: the explicit RPC
        # fan-out (mesh disabled) agrees with what the typed fallback
        # already served mid-crash
        cluster.call(lambda cb: client.cluster_update_settings(
            {"persistent": {"search.mesh.enabled": False}}, cb))
        resp3, err = cluster.call(
            lambda cb: client.search("m", copy.deepcopy(body2), cb),
            max_time=180.0)
        assert err is None, err
        assert resp3.get("_data_plane") == "batch"
        assert resp2["hits"] == resp3["hits"]
    finally:
        cluster.stop()


@pytest.mark.parametrize("seed", [23 + 449 * k for k in range(CHAOS_SEEDS)])
def test_e2e_dfs_mesh_parity(seed):
    """dfs_query_then_fetch rides the mesh: the coordinator's gathered
    global df / avgdl overrides thread into the mesh BM25 kernel, and
    responses are byte-identical to the DFS RPC fan-out."""
    cluster, client, rng = _e2e_cluster(seed)
    try:
        bodies = [
            {"query": {"match": {"body": "w1 w3 w7"}}, "size": 8},
            {"query": {"match": {"body": "w2 w4"}}, "size": 5,
             "track_total_hits": False},
            {"query": {"match": {"body": "w5 w9"}}, "size": 6,
             "track_total_hits": 7},
        ]
        mesh_resps = []
        for body in bodies:
            resp, err = cluster.call(
                lambda cb, b=body: client.search(
                    "m", copy.deepcopy(b), cb,
                    search_type="dfs_query_then_fetch"))
            assert err is None, (body, err)
            assert resp.get("_data_plane") == "mesh_plane", \
                (body, resp.get("_data_plane"))
            mesh_resps.append(resp)
        cluster.call(lambda cb: client.cluster_update_settings(
            {"persistent": {"search.mesh.enabled": False}}, cb))
        for body, mesh_resp in zip(bodies, mesh_resps):
            resp, err = cluster.call(
                lambda cb, b=body: client.search(
                    "m", copy.deepcopy(b), cb,
                    search_type="dfs_query_then_fetch"))
            assert err is None, (body, err)
            assert resp.get("_data_plane") == "dense"   # DFS overrides
            a = {k: v for k, v in mesh_resp.items()
                 if k not in ("took", "_data_plane")}
            b = {k: v for k, v in resp.items() if k not in ("took", "_data_plane")}
            assert json.dumps(a, sort_keys=True) == \
                json.dumps(b, sort_keys=True), body
    finally:
        cluster.stop()
