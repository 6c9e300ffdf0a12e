"""The SPMD mesh data plane behind TransportSearchAction.

VERDICT r2 #1b: when the node drives a multi-device mesh and holds every
shard of the index, eligible whole-index searches must run as ONE pjit
program (parallel/mesh_plane.py) — asserted via the response's _data_plane
marker — and agree with the host-RPC scatter-gather path.
"""

import numpy as np
import pytest

from elasticsearch_tpu.testing import InProcessCluster


@pytest.fixture()
def cluster():
    c = InProcessCluster(n_nodes=1, seed=3, mesh_data_plane=True)
    c.start()
    yield c
    c.stop()


def _ok(resp, err):
    assert err is None, f"unexpected error: {err}"
    return resp


WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa"]


def _index_corpus(cluster, client, name="mesh", n=60, shards=3):
    cluster.call(lambda cb: client.create_index(
        name, {"settings": {"number_of_shards": shards,
                            "number_of_replicas": 0}}, cb))
    cluster.ensure_green(name)
    rng = np.random.default_rng(7)
    for i in range(n):
        text = " ".join(rng.choice(WORDS, size=int(rng.integers(3, 9))))
        resp, err = cluster.call(lambda cb, i=i, text=text: client.index_doc(
            name, f"d{i}", {"body": text, "n": i}, cb))
        _ok(resp, err)
    cluster.call(lambda cb: client.refresh(name, cb))


def test_mesh_path_serves_eligible_search(cluster):
    client = cluster.client()
    _index_corpus(cluster, client)

    q = {"query": {"match": {"body": "alpha gamma"}}, "size": 8}
    # unbounded exact counting still demands the RPC path
    rpc, err = cluster.call(lambda cb: client.search(
        "mesh", {**q, "track_total_hits": True}, cb))
    _ok(rpc, err)
    assert rpc["_data_plane"] == "dense"   # unbounded totals: per member
    # the DEFAULT totals threshold is mesh-served with EXACT counts
    # (counts-then-skip over the sharded program)
    default, err = cluster.call(lambda cb: client.search("mesh", q, cb))
    _ok(default, err)
    assert default.get("_data_plane") == "mesh"
    assert default["hits"]["total"] == rpc["hits"]["total"]
    # a tiny threshold clips with gte
    clipped, err = cluster.call(lambda cb: client.search(
        "mesh", {**q, "track_total_hits": 3}, cb))
    _ok(clipped, err)
    assert clipped.get("_data_plane") == "mesh"
    assert clipped["hits"]["total"] == {"value": 3, "relation": "gte"}

    # the mesh program scores with exact GLOBAL idf, so the apples-to-apples
    # host-path comparison is dfs_query_then_fetch (which pre-shares global
    # term stats); plain query_then_fetch uses shard-local idf by design
    dfs, err = cluster.call(lambda cb: client.search(
        "mesh", q, cb, search_type="dfs_query_then_fetch"))
    _ok(dfs, err)

    mesh, err = cluster.call(lambda cb: client.search(
        "mesh", {**q, "track_total_hits": False}, cb))
    _ok(mesh, err)
    assert mesh.get("_data_plane") == "mesh"
    assert set(h["_id"] for h in mesh["hits"]["hits"]) == \
        set(h["_id"] for h in dfs["hits"]["hits"])
    np.testing.assert_allclose(
        [h["_score"] for h in mesh["hits"]["hits"]],
        [h["_score"] for h in dfs["hits"]["hits"]], rtol=1e-5, atol=1e-5)
    # full hits come back through the normal fetch phase
    assert all("_source" in h for h in mesh["hits"]["hits"])

    stats = cluster.master().mesh_plane.stats
    assert stats["mesh_queries"] >= 3 and stats["mesh_builds"] == 1


def test_mesh_cache_invalidated_on_change(cluster):
    client = cluster.client()
    _index_corpus(cluster, client, name="inv", n=30, shards=2)
    body = {"query": {"match": {"body": "beta"}},
            "track_total_hits": False, "size": 5}
    r1, err = cluster.call(lambda cb: client.search("inv", body, cb))
    _ok(r1, err)
    assert r1.get("_data_plane") == "mesh"
    builds0 = cluster.master().mesh_plane.stats["mesh_builds"]

    # same snapshot: cache hit
    r2, err = cluster.call(lambda cb: client.search("inv", body, cb))
    _ok(r2, err)
    assert cluster.master().mesh_plane.stats["mesh_builds"] == builds0

    # new doc + refresh: rebuild, and the new doc is findable via mesh
    resp, err = cluster.call(lambda cb: client.index_doc(
        "inv", "fresh", {"body": "omicronunique beta"}, cb))
    _ok(resp, err)
    cluster.call(lambda cb: client.refresh("inv", cb))
    r3, err = cluster.call(lambda cb: client.search(
        "inv", {"query": {"match": {"body": "omicronunique"}},
                "track_total_hits": False, "size": 5}, cb))
    _ok(r3, err)
    assert r3.get("_data_plane") == "mesh"
    assert [h["_id"] for h in r3["hits"]["hits"]] == ["fresh"]
    assert cluster.master().mesh_plane.stats["mesh_builds"] > builds0


def test_mesh_respects_deletes(cluster):
    client = cluster.client()
    _index_corpus(cluster, client, name="del", n=20, shards=2)
    r1, err = cluster.call(lambda cb: client.search(
        "del", {"query": {"match": {"body": "alpha"}},
                "track_total_hits": False, "size": 20}, cb))
    _ok(r1, err)
    got = [h["_id"] for h in r1["hits"]["hits"]]
    if not got:
        pytest.skip("corpus draw has no alpha docs")
    victim = got[0]
    resp, err = cluster.call(lambda cb: client.delete_doc("del", victim, cb))
    _ok(resp, err)
    cluster.call(lambda cb: client.refresh("del", cb))
    r2, err = cluster.call(lambda cb: client.search(
        "del", {"query": {"match": {"body": "alpha"}},
                "track_total_hits": False, "size": 20}, cb))
    _ok(r2, err)
    assert r2.get("_data_plane") == "mesh"
    assert victim not in [h["_id"] for h in r2["hits"]["hits"]]


def test_ineligible_queries_fall_back_to_rpc(cluster):
    client = cluster.client()
    _index_corpus(cluster, client, name="fb", n=20, shards=2)
    for body in (
        {"query": {"bool": {"must": [{"match": {"body": "alpha"}}]}},
         "track_total_hits": False},
        {"query": {"match": {"body": "alpha"}},
         "track_total_hits": True},                   # unbounded exact
        {"query": {"match": {"body": "alpha"}},
         "track_total_hits": False, "sort": [{"n": "asc"}]},
        {"query": {"match": {"body": "alpha"}},
         "track_total_hits": False,
         "aggs": {"m": {"max": {"field": "n"}}}},
    ):
        resp, err = cluster.call(lambda cb, b=body: client.search(
            "fb", b, cb))
        _ok(resp, err)
        assert resp.get("_data_plane") not in ("mesh", "mesh_plane"), body


def test_mesh_serves_bool_should(cluster):
    """Bool of only-should Match clauses (with boosts) rides the mesh
    plane and agrees with the DFS host path."""
    client = cluster.client()
    _index_corpus(cluster, client, name="bs", n=40, shards=2)
    body = {"query": {"bool": {"should": [
        {"match": {"body": {"query": "alpha", "boost": 2.0}}},
        {"match": {"body": "gamma delta"}}]}}, "size": 8,
        "track_total_hits": False}
    mesh, err = cluster.call(lambda cb: client.search("bs", body, cb))
    _ok(mesh, err)
    assert mesh.get("_data_plane") == "mesh"
    dfs, err = cluster.call(lambda cb: client.search(
        "bs", body, cb, search_type="dfs_query_then_fetch"))
    _ok(dfs, err)
    assert set(h["_id"] for h in mesh["hits"]["hits"]) == \
        set(h["_id"] for h in dfs["hits"]["hits"])
    np.testing.assert_allclose(
        [h["_score"] for h in mesh["hits"]["hits"]],
        [h["_score"] for h in dfs["hits"]["hits"]], rtol=1e-5, atol=1e-5)


def test_mesh_serves_knn(cluster):
    """Unfiltered kNN queries run as one mesh program (VERDICT r3 weak #3:
    the kernels existed but mesh_eligible never routed them)."""
    client = cluster.client()
    cluster.call(lambda cb: client.create_index("vecs", {
        "settings": {"number_of_shards": 2, "number_of_replicas": 0},
        "mappings": {"properties": {
            "vec": {"type": "dense_vector", "dims": 8,
                    "similarity": "cosine"}}}}, cb))
    cluster.ensure_green("vecs")
    rng = np.random.default_rng(11)
    vecs = rng.standard_normal((30, 8)).astype(np.float32)
    for i in range(30):
        resp, err = cluster.call(lambda cb, i=i: client.index_doc(
            "vecs", f"v{i}", {"vec": vecs[i].tolist()}, cb))
        _ok(resp, err)
    cluster.call(lambda cb: client.refresh("vecs", cb))

    qv = rng.standard_normal(8).astype(np.float32)
    body = {"query": {"knn": {"field": "vec", "query_vector": qv.tolist(),
                              "k": 5, "num_candidates": 30}}, "size": 5}
    mesh, err = cluster.call(lambda cb: client.search("vecs", body, cb))
    _ok(mesh, err)
    assert mesh.get("_data_plane") == "mesh"
    # parity with the RPC per-shard path (cosine brute force, same transform)
    sims = (vecs @ qv) / (np.linalg.norm(vecs, axis=1)
                          * np.linalg.norm(qv) + 1e-30)
    expect = [f"v{i}" for i in np.argsort(-sims)[:5]]
    assert [h["_id"] for h in mesh["hits"]["hits"]] == expect


def test_mesh_serves_text_expansion(cluster):
    """text_expansion with precomputed tokens runs as one mesh program
    over the sharded rank-features blocks."""
    client = cluster.client()
    cluster.call(lambda cb: client.create_index("sp", {
        "settings": {"number_of_shards": 2, "number_of_replicas": 0},
        "mappings": {"properties": {
            "feats": {"type": "rank_features"}}}}, cb))
    cluster.ensure_green("sp")
    rng = np.random.default_rng(13)
    feats = [f"f{i}" for i in range(12)]
    docs = []
    for i in range(24):
        chosen = rng.choice(feats, size=int(rng.integers(2, 6)),
                            replace=False)
        docs.append({f: float(rng.uniform(0.5, 3.0)) for f in chosen})
        resp, err = cluster.call(lambda cb, i=i: client.index_doc(
            "sp", f"s{i}", {"feats": docs[i]}, cb))
        _ok(resp, err)
    cluster.call(lambda cb: client.refresh("sp", cb))

    tokens = {"f1": 1.5, "f3": 0.7, "f8": 2.0}
    body = {"query": {"text_expansion": {"feats": {
        "tokens": tokens}}}, "size": 6}
    mesh, err = cluster.call(lambda cb: client.search("sp", body, cb))
    _ok(mesh, err)
    assert mesh.get("_data_plane") == "mesh"
    # parity with host linear scoring
    truth = []
    for i, d in enumerate(docs):
        sc = sum(w * d.get(f, 0.0) for f, w in tokens.items())
        if sc > 0:
            truth.append((sc, f"s{i}"))
    truth.sort(key=lambda x: (-x[0], x[1]))
    expect = [t[1] for t in truth[:6]]
    got = [h["_id"] for h in mesh["hits"]["hits"]]
    assert set(got) == set(expect)


def test_mesh_build_cost_is_observable(cluster):
    """VERDICT r3 weak #8: refresh-heavy workloads rebuild the mesh copy;
    the rebuild price must be measurable, not invisible."""
    client = cluster.client()
    _index_corpus(cluster, client, name="bt", n=30, shards=2)
    body = {"query": {"match": {"body": "beta"}},
            "track_total_hits": False, "size": 5}
    r, err = cluster.call(lambda cb: client.search("bt", body, cb))
    _ok(r, err)
    stats = cluster.master().mesh_plane.stats
    assert stats["mesh_builds"] >= 1
    assert stats["last_build_seconds"] > 0
    assert stats["last_build_docs"] == 30
    assert stats["build_seconds_total"] >= stats["last_build_seconds"]
    before = stats["mesh_builds"]
    # a refresh-invalidating write triggers exactly one more build
    r, err = cluster.call(lambda cb: client.index_doc(
        "bt", "new", {"body": "beta fresh"}, cb))
    _ok(r, err)
    cluster.call(lambda cb: client.refresh("bt", cb))
    r, err = cluster.call(lambda cb: client.search("bt", body, cb))
    _ok(r, err)
    assert stats["mesh_builds"] == before + 1
    assert stats["last_build_docs"] == 31


def test_mesh_knn_total_clamped_to_hits_returned():
    """ADVICE r5 medium: the kNN hit window (size+from) is not bounded by
    query.k, so the reported total must clamp to at least the number of
    hits actually returned — hits > total is an incoherent response no
    other plane produces. Drives search_knn against a stub vector index
    so the invariant is tested without mesh hardware."""
    from types import SimpleNamespace

    from elasticsearch_tpu.parallel.mesh_plane import MeshDataPlane
    from elasticsearch_tpu.search import dsl

    plane = MeshDataPlane(mesh=object())   # "available" without devices
    n_docs = 1000

    class StubVectorIndex:
        n_docs = 1000

        def search(self, qv, k):
            scores = np.linspace(2.0, 1.0, k, dtype=np.float32)[None, :]
            ids = np.arange(k, dtype=np.int32)[None, :]
            return scores, ids

    id_map = (np.zeros(n_docs, np.int32), np.zeros(n_docs, np.int32),
              np.arange(n_docs, dtype=np.int32))
    shard_counts = np.array([n_docs])
    plane._vector_index = lambda *a: (StubVectorIndex(), id_map,
                                      shard_counts)
    shard = SimpleNamespace(engine=SimpleNamespace(
        acquire_reader=lambda: None))
    query = dsl.Knn(field="vec", query_vector=[0.0, 1.0], k=10)

    result = plane.search_knn("idx", "vec", {0: shard},
                              {"size": 100}, query)
    assert len(result["hits"]) == 100
    # pre-fix: total = min(1000, k=10) = 10 < 100 hits
    assert result["total"] >= len(result["hits"])
    assert result["relation"] == "eq"
