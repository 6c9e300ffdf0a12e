"""Segments built from arrays and installed through snapshot restore
serve the same top-10 ids and scores as the same docs sent through
``_bulk``, for each configuration, at 2^12 docs; a four-shard index's
docs lie in the shards ``_bulk`` routes their ``_id`` to."""

import json

import numpy as np
import pytest

from benchmark import harness
from benchmark.run import HERE, load_module

DOCS = 1 << 12
N_QUERIES = 24


def ndjson_msmarco(data, lo, hi) -> str:
    out = []
    for i in range(lo, hi):
        out.append(json.dumps({"index": {"_id": str(i)}}) + "\n"
                   + json.dumps(data.source(i)) + "\n")
    return "".join(out)


def ndjson_sift(data, lo, hi) -> str:
    return "".join(json.dumps({"index": {"_id": str(i)}}) + "\n"
                   + json.dumps({"vec": data.vectors[i].tolist()}) + "\n"
                   for i in range(lo, hi))


def ndjson_msmarco_4shard(data, lo, hi) -> str:
    """Generator docs [lo, hi) under their routed ``_id``s."""
    return "".join(json.dumps({"index": {"_id": str(i)}}) + "\n"
                   + json.dumps(data.source(i)) + "\n"
                   for i in data.ids[lo:hi].tolist())


CASES = [
    ("msmarco-passage", ndjson_msmarco, ("bm25.serial",)),
    ("sift1m", ndjson_sift, ("knn.open",)),
    ("msmarco-passage-4shard", ndjson_msmarco_4shard, ("bm25.mesh4.open",)),
]


@pytest.mark.parametrize("config,ndjson,traffics", CASES,
                         ids=[c[0] for c in CASES])
def test_array_segments_serve_like_bulk(config, ndjson, traffics):
    cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
    mod = load_module(HERE / "configs" / f"{config}.py", f"cfg_{config}")
    data = mod.Data(cfg, 2 ** 33 + 5, n_docs=DOCS)
    srv = harness.Served()
    try:
        harness.create_index(srv, data.index, data.index_body())
        harness.install_shards(srv, data.index, data)
        harness.create_index(srv, "bulk", data.index_body())
        # one bulk and refresh per segment: a segment's docs all route
        # to its shard, so each shard gets the same segments in order
        per_seg = DOCS // int(cfg["segments"]) // int(cfg.get("shards", 1))
        for lo in range(0, DOCS, per_seg):
            out = srv.ok("POST", "/bulk/_bulk", ndjson(data, lo, lo + per_seg),
                         ndjson=True)
            assert not out.get("errors")
            srv.ok("POST", "/bulk/_refresh")
        for name in traffics:
            traffic = json.loads(
                (HERE / "traffic" / f"{name}.json").read_text())
            pool = data.pool(traffic, 11)[:N_QUERIES]
            for spec in pool:
                body = data.body(spec)
                a = srv.ok("POST", f"/{data.index}/_search", body)
                b = srv.ok("POST", "/bulk/_search", body)
                hits_a = [(h["_id"], h["_score"]) for h in a["hits"]["hits"]]
                hits_b = [(h["_id"], h["_score"]) for h in b["hits"]["hits"]]
                assert hits_a, f"{name}: no hits for {spec}"
                assert hits_a == hits_b, f"{name}: {spec}"
    finally:
        srv.close()


def test_reference_agrees_with_served_scores():
    """The numpy reference scores the served top-10 within f32
    rounding (a check of the reference, not of the limits)."""
    cfg = json.loads((HERE / "configs" / "msmarco-passage.json").read_text())
    mod = load_module(HERE / "configs" / "msmarco-passage.py", "cfg_ms")
    data = mod.Data(cfg, 3, n_docs=DOCS)
    traffic = json.loads((HERE / "traffic" / "bm25.serial.json").read_text())
    pool = data.pool(traffic, 4)[:N_QUERIES]
    srv = harness.Served()
    try:
        harness.create_index(srv, data.index, data.index_body())
        harness.install_segments(srv, data.index, data.build_segments)
        resps = [srv.ok("POST", f"/{data.index}/_search", data.body(s))
                 for s in pool]
    finally:
        srv.close()
    refs = data.reference(pool)
    got = harness.compare(resps, lambda i: refs[i], data.source, data.n_docs)
    assert got["rank_gap"] < 1e-5 and got["score_gap"] < 1e-5, got
    assert got["fetch_errors"] == 0 and got["lost"] == 0, got
    assert np.isfinite(got["score_gap"])


def test_routing_hash_is_the_bulk_routing():
    """The four-shard configuration's vectorized murmur3 routes every
    ``_id`` to the shard ``_bulk`` routes it to."""
    from elasticsearch_tpu.utils.murmur3 import shard_id_for
    mod = load_module(HERE / "configs" / "msmarco-passage-4shard.py",
                      "cfg_4shard")
    n = np.arange(0, 200_000, 7)
    got = mod.murmur3_decimal(n) % np.uint32(4)
    want = [shard_id_for(str(i), 4) for i in n.tolist()]
    assert got.tolist() == want
