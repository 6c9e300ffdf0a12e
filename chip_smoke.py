#!/usr/bin/env python3
"""Bring-up smoke: the served search path on a TPU, end to end.

One process boots one node the way ``python -m elasticsearch_tpu.rest.server``
does (``Node`` on the threaded scheduler, HTTP on 127.0.0.1), loads a seeded
corpus through ``_bulk`` and serves searches of every class over HTTP:

- default (one chip): a 1-shard index (2^20 docs in the configuration,
  cut to 2^19 by default; ``--docs`` sets it, and a cut prints
  ``reduced``) shaped like MS MARCO passages (``body``),
  SIFT1M vectors (``vec``, 128-d cosine, exact ``flat`` index) and ELSER
  expansions (``feats``, rank_features), refreshed 4 times; BM25
  ``match``, kNN, filtered kNN, hybrid RRF, sparse ``text_expansion`` and a
  size-0 ``terms`` aggregation, each checked for its device data plane, a
  rising device-dispatch count, zero error fallbacks, and against a plain
  numpy reference; a concurrent kNN wave must equal the same queries solo.
- ``--four-chips``: one 4-shard index of the same corpus (2^18 docs per
  shard, refreshed once) served by the mesh-sharded program over 4
  devices, compared with the per-shard fan-out.

It exits non-zero, printing no result line, when JAX finds no TPU, when
the package beside it is missing, or when any check fails. The last line
of a passing run is the contract:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Timings printed on earlier lines are bring-up facts, not metrics.
"""

from __future__ import annotations

import argparse
import asyncio
import http.client
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

SEED = 21
FULL_DOCS = 1 << 20        # the configuration's corpus, either phase
# The one-chip default is 2^19 docs, the largest corpus this smoke has
# finished on one chip (336.4 s, ingest 1848 docs/s); 2^20 has not been
# run on one chip.
ONE_CHIP_DOCS = 1 << 19
VOCAB = 1 << 17            # body vocabulary (zipf)
MEAN_LEN = 56              # MS MARCO passage tokens
DIMS = 128                 # SIFT1M
FEAT_VOCAB = 30522         # ELSER's BERT wordpiece vocabulary
FEATS_PER_DOC = 40         # before de-duplication: tens of features
TAGS = 32                  # keyword the terms aggregation buckets
REFRESHES = 4              # >= 2 segments: the packed plane engages
MESH_REFRESHES = 1         # one 2^18-doc segment per shard: the mesh
                           # needs no packed plane, and the segments are
                           # ANN-sized (>= 64K docs) at any cut >= 280000
BULK_DOCS = 8192
K = 10
N_QUERIES = 8              # per class: 1 cold + 7 warm
INDEX_MAPPING = {"properties": {
    "body": {"type": "text"},
    # "flat": exact brute force at any segment size (ES dense_vector
    # index_options): no IVF route, no int8 coarse tier
    "vec": {"type": "dense_vector", "dims": DIMS, "similarity": "cosine",
            "index_options": {"type": "flat"}},
    "feats": {"type": "rank_features"},
    "tag": {"type": "keyword"}}}
# error fallbacks that must stay at zero on every class
ERROR_REASONS = ("batch_exec_error", "mesh_drain_error", "unknown")


def log(*parts) -> None:
    print(*parts, flush=True)


class Failures:
    def __init__(self):
        self.items = []

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.items.append(what)
            log(f"FAIL {what}")
        return ok


# ---------------------------------------------------------------------------
# the seeded corpus (numpy, in bulk) and its NDJSON
# ---------------------------------------------------------------------------

class Corpus:
    """``n`` documents from ``seed``: token ids (zipf over VOCAB, gamma
    lengths around MEAN_LEN), SIFT-like uint8 vectors (clustered,
    non-negative integers, exact in bf16), ELSER-like sparse features
    (zipf ids, weights in hundredths) and a zipf keyword tag."""

    def __init__(self, n: int, seed: int = SEED):
        rng = np.random.default_rng(seed)
        self.n = n
        lens = np.clip(np.rint(rng.gamma(4.0, MEAN_LEN / 4.0, n)), 4, 256)
        self.offsets = np.zeros(n + 1, np.int64)
        np.cumsum(lens.astype(np.int64), out=self.offsets[1:])
        cdf = np.cumsum(1.0 / np.arange(1, VOCAB + 1) ** 1.05)
        cdf /= cdf[-1]
        self.tokens = np.searchsorted(
            cdf, rng.random(int(self.offsets[-1]))).astype(np.int32)
        self.doc_of = np.repeat(np.arange(n, dtype=np.int32),
                                np.diff(self.offsets))
        centers = rng.gamma(0.7, 30.0, (1024, DIMS))
        self.vectors = np.empty((n, DIMS), np.uint8)
        for a in range(0, n, 1 << 16):
            b = min(n, a + (1 << 16))
            x = centers[rng.integers(0, len(centers), b - a)] + \
                rng.normal(0.0, 9.0, (b - a, DIMS))
            self.vectors[a:b] = np.clip(np.rint(x), 0, 255)
        self.centers = centers
        fcdf = np.cumsum(1.0 / np.arange(1, FEAT_VOCAB + 1) ** 0.8)
        fcdf /= fcdf[-1]
        fid = np.sort(np.searchsorted(
            fcdf, rng.random((n, FEATS_PER_DOC))), axis=1).astype(np.int32)
        keep = np.ones_like(fid, bool)
        keep[:, 1:] = fid[:, 1:] != fid[:, :-1]
        fw = np.clip(np.rint(rng.gamma(2.0, 40.0, fid.shape)), 1, 300)
        self.feat_ids, self.feat_w100 = fid, fw.astype(np.int32)
        self.feat_keep = keep
        tcdf = np.cumsum(1.0 / np.arange(1, TAGS + 1))
        tcdf /= tcdf[-1]
        self.tags = np.searchsorted(tcdf, rng.random(n)).astype(np.int32)

    def ndjson(self, a: int, b: int) -> bytes:
        """Bulk NDJSON of docs [a, b): ``_id`` is the doc's index."""
        if not hasattr(self, "_words"):
            self._words = [f"w{t}" for t in range(VOCAB)]
            self._fkey = [f'"f{f}":' for f in range(FEAT_VOCAB)]
            self._wstr = [f"{w / 100:.2f}" for w in range(301)]
            # fixed-width "%3d," per component: JSON allows the spaces
            self._lut = np.frombuffer(b"".join(
                f"{v:>3},".encode() for v in range(256)),
                np.uint8).reshape(256, 4)
        words, fkey, wstr, lut = self._words, self._fkey, self._wstr, \
            self._lut
        vec_rows = np.empty((b - a, 1 + 4 * DIMS), np.uint8)
        vec_rows[:, 0] = ord("[")
        vec_rows[:, 1:] = lut[self.vectors[a:b]].reshape(b - a, -1)
        vec_rows[:, -1] = ord("]")
        toks = self.tokens[self.offsets[a]: self.offsets[b]].tolist()
        base = int(self.offsets[a])
        out = []
        for i in range(a, b):
            s, e = int(self.offsets[i]) - base, int(self.offsets[i + 1]) - base
            keep = self.feat_keep[i]
            feats = ",".join(fkey[f] + wstr[w] for f, w in zip(
                self.feat_ids[i][keep].tolist(),
                self.feat_w100[i][keep].tolist()))
            out.append('{"index":{"_id":"%d"}}\n{"body":"%s","vec":%s,'
                       '"feats":{%s},"tag":"t%d"}\n' % (
                           i, " ".join(words[t] for t in toks[s:e]),
                           vec_rows[i - a].tobytes().decode(), feats,
                           self.tags[i]))
        return "".join(out).encode()


class Queries:
    """N_QUERIES seeded queries of each shape: 3-term ``match`` over
    mid-frequency terms (MS MARCO-like), vectors from the corpus'
    clusters, a tag filter, 12-token ELSER-like expansions."""

    def __init__(self, corpus: Corpus, seed: int):
        rng = np.random.default_rng(seed)
        self.terms = [[int(t) for t in rng.choice(np.arange(30, 6000), 3,
                                                  replace=False)]
                      for _ in range(N_QUERIES)]
        x = corpus.centers[rng.integers(0, len(corpus.centers),
                                        N_QUERIES)] + \
            rng.normal(0.0, 9.0, (N_QUERIES, DIMS))
        self.vecs = np.clip(np.rint(x), 0, 255).astype(np.float32)
        self.tags = rng.integers(0, 4, N_QUERIES)
        self.feats = [{int(f): round(float(w), 2) for f, w in zip(
            rng.choice(np.arange(0, 2000), 12, replace=False),
            rng.uniform(0.2, 2.0, 12))} for _ in range(N_QUERIES)]

    def match(self, i: int) -> dict:
        return {"match": {"body": " ".join(f"w{t}" for t in self.terms[i])}}

    def knn(self, i: int, filtered: bool = False) -> dict:
        clause = {"field": "vec", "query_vector": self.vecs[i].tolist(),
                  "k": K, "num_candidates": 100}
        if filtered:
            clause["filter"] = {"term": {"tag": f"t{self.tags[i]}"}}
        return clause

    def sparse(self, i: int) -> dict:
        return {"text_expansion": {"feats": {"tokens": {
            f"f{f}": w for f, w in self.feats[i].items()}}}}


# top-k bodies skip both request caches: every query is served
UNCACHED = {"size": K, "request_cache": False}


# ---------------------------------------------------------------------------
# plain numpy references
# ---------------------------------------------------------------------------

def bm25_oracle(corpus: Corpus, seg_bounds, terms) -> np.ndarray:
    """f64 BM25 over the generated tokens with the engine's statistics:
    shard-level doc count and df (idf), per-segment avgdl (norms)."""
    k1, b = 1.2, 0.75
    n = corpus.n
    lens = np.diff(corpus.offsets).astype(np.float64)
    avgdl = np.empty(n)
    for lo, hi in seg_bounds:
        avgdl[lo:hi] = lens[lo:hi].mean()
    scores = np.zeros(n)
    for t in terms:
        docs = corpus.doc_of[corpus.tokens == t]
        if docs.size == 0:
            continue
        tf = np.bincount(docs, minlength=n).astype(np.float64)
        df = np.count_nonzero(tf)
        idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        hit = tf > 0
        norm = k1 * (1.0 - b + b * lens[hit] / avgdl[hit])
        scores[hit] += idf * tf[hit] * (k1 + 1.0) / (tf[hit] + norm)
    return scores


def cosine_oracle(corpus: Corpus, queries: np.ndarray,
                  allowed=None) -> np.ndarray:
    """[Q, N] exact f32 cosine similarity (ranking only)."""
    out = np.empty((len(queries), corpus.n), np.float32)
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    for a in range(0, corpus.n, 1 << 18):
        m = corpus.vectors[a: a + (1 << 18)].astype(np.float32)
        m /= np.maximum(np.linalg.norm(m, axis=1, keepdims=True), 1e-30)
        out[:, a: a + (1 << 18)] = qn @ m.T
    if allowed is not None:
        out[:, ~allowed] = -np.inf
    return out


def sparse_oracle(corpus: Corpus, tokens: dict) -> np.ndarray:
    scores = np.zeros(corpus.n)
    ids = np.where(corpus.feat_keep, corpus.feat_ids, -1)
    for f, qw in tokens.items():
        rows, cols = np.nonzero(ids == f)
        scores[rows] += qw * corpus.feat_w100[rows, cols] / 100.0
    return scores


def topk_matches(got_ids, got_scores, ref: np.ndarray, k: int) -> str:
    """'' when the engine's top-k equals the reference's, allowing swaps
    only among docs whose reference scores tie (relative 1e-5); else why
    not."""
    order = np.argsort(-ref, kind="stable")
    want = order[:k][ref[order[:k]] > 0]
    if len(got_ids) != len(want):
        return f"{len(got_ids)} hits, reference has {len(want)}"
    tol = 1e-5 * max(float(ref[want[0]]) if len(want) else 1.0, 1.0)
    kth = float(ref[want[-1]]) if len(want) else 0.0
    for i, (d, s) in enumerate(zip(got_ids, got_scores)):
        if abs(ref[d] - ref[want[i]]) > tol:
            return (f"rank {i}: doc {d} scores {ref[d]:.6f}, reference "
                    f"doc {want[i]} scores {ref[want[i]]:.6f}")
        if abs(float(s) - ref[d]) > 1e-4 * max(abs(ref[d]), 1.0):
            return f"doc {d}: engine score {s} vs reference {ref[d]:.6f}"
    missing = set(want.tolist()) - set(got_ids)
    if any(ref[d] > kth + tol for d in missing):
        return f"missing reference docs {sorted(missing)}"
    return ""


# ---------------------------------------------------------------------------
# the node over HTTP
# ---------------------------------------------------------------------------

class Served:
    """One node booted like ``rest.server.run_single_node`` with its HTTP
    server on an event loop thread; ``request`` is a keep-alive client."""

    def __init__(self):
        from elasticsearch_tpu.rest.server import HttpServer, boot_single_node
        self.node = boot_single_node()
        self.server = HttpServer(self.node.client, "127.0.0.1", 0)
        self.loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.server.start())
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, name="http", daemon=True)
        self.thread.start()
        if not started.wait(60):
            raise RuntimeError("HTTP server did not start")
        self._local = threading.local()
        self._conns = []

    def request(self, method: str, path: str, body=None,
                ndjson: bool = False):
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = self._local.conn = http.client.HTTPConnection(
                "127.0.0.1", self.server.port, timeout=1800)
            self._conns.append(conn)
        if body is not None and not isinstance(body, (bytes, str)):
            body = json.dumps(body)
        ctype = "application/x-ndjson" if ndjson else "application/json"
        conn.request(method, path, body=body,
                     headers={"content-type": ctype})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, (json.loads(raw) if raw else None)

    def ok(self, method: str, path: str, body=None, ndjson: bool = False):
        status, out = self.request(method, path, body, ndjson)
        if status != 200:
            raise RuntimeError(f"{method} {path} -> {status}: "
                               f"{str(out)[:500]}")
        return out

    def stats(self) -> dict:
        nodes = self.ok("GET", "/_nodes/stats")["nodes"]
        return next(iter(nodes.values()))

    def close(self) -> None:
        for conn in self._conns:   # the server's stop waits for them
            conn.close()
        try:
            asyncio.run_coroutine_threadsafe(
                self.server.stop(), self.loop).result(30)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(30)
            self.node.stop()


def load(srv: Served, corpus: Corpus, index: str, shards: int,
         refreshes: int = REFRESHES) -> list:
    """Create ``index``, ingest through _bulk in ``refreshes`` parts with
    a refresh after each; returns each part's [lo, hi) doc range."""
    srv.ok("PUT", f"/{index}", {
        "settings": {"number_of_shards": shards, "number_of_replicas": 0},
        "mappings": INDEX_MAPPING})
    bounds = np.linspace(0, corpus.n, refreshes + 1).astype(int)
    t0, t_gen = time.perf_counter(), 0.0
    for part in range(refreshes):
        lo, hi = int(bounds[part]), int(bounds[part + 1])
        for a in range(lo, hi, BULK_DOCS):
            g0 = time.perf_counter()
            payload = corpus.ndjson(a, min(hi, a + BULK_DOCS))
            t_gen += time.perf_counter() - g0
            out = srv.ok("POST", f"/{index}/_bulk", payload, ndjson=True)
            if out.get("errors"):
                bad = next(i for i in out["items"]
                           if next(iter(i.values())).get("error"))
                raise RuntimeError(f"bulk item failed: {bad}")
        r0 = time.perf_counter()
        srv.ok("POST", f"/{index}/_refresh")
        log(f"ingest: part {part + 1}/{refreshes} docs [{lo},{hi}) "
            f"refreshed in {time.perf_counter() - r0:.1f}s")
    took = time.perf_counter() - t0
    log(f"ingest: {corpus.n} docs into {index} ({shards} shard(s)) in "
        f"{took:.1f}s (NDJSON generation {t_gen:.1f}s), "
        f"{corpus.n / max(took - t_gen, 1e-9):.0f} docs/s served")
    return [(int(bounds[i]), int(bounds[i + 1])) for i in range(refreshes)]


def class_dispatches(stats: dict, cls: str, plane: str) -> int:
    e = stats["search_latency"]["classes"].get(f"{cls}|{plane}") or {}
    return int(e.get("device_dispatches", 0))


def run_class(srv: Served, fails: Failures, device: str, name: str,
              index: str, bodies: list, want_plane: str, counted: tuple):
    """Send ``bodies`` one by one; the first pays compiles (and the
    plane build). Checks status, ``_data_plane``, the rising dispatch
    count of ``counted`` (class, plane) histogram keys and the error
    fallbacks. Returns the responses."""
    before = srv.stats()
    resps, lat = [], []
    for body in bodies:
        t0 = time.perf_counter()
        status, resp = srv.request("POST", f"/{index}/_search", body)
        lat.append(time.perf_counter() - t0)
        fails.check(status == 200, f"{name}: status {status} "
                                   f"{str(resp)[:300]}")
        resps.append(resp if status == 200 else None)
    after = srv.stats()
    for resp in resps:
        if resp is None:
            continue
        fails.check(resp.get("_data_plane") == want_plane,
                    f"{name}: _data_plane {resp.get('_data_plane')!r}, "
                    f"want {want_plane!r}")
        fails.check(resp["_shards"]["failed"] == 0,
                    f"{name}: shard failures {resp['_shards']}")
    rose = sum(class_dispatches(after, c, p) - class_dispatches(before, c, p)
               for c, p in counted)
    fails.check(rose > 0, f"{name}: device dispatches of {counted} did not "
                          f"rise")
    falls = after["search_latency"]["fallback_reasons"]
    for reason in ERROR_REASONS:
        fails.check(not falls.get(reason),
                    f"{name}: fallback {reason} = {falls.get(reason)}")
    warm = sorted(lat[1:]) or lat
    log(f"{name}: [{device}] first query {lat[0]:.3f}s (compile + plane "
        f"build), warm median {1e3 * warm[len(warm) // 2]:.2f} ms over "
        f"{len(warm)} queries, device dispatches +{rose}, "
        f"_data_plane {resps[0].get('_data_plane') if resps[0] else None}")
    return resps


def hit_ids(resp) -> list:
    return [int(h["_id"]) for h in resp["hits"]["hits"]]


def hit_scores(resp) -> list:
    return [float(h["_score"]) for h in resp["hits"]["hits"]]


def recall(resps, ref: np.ndarray, k: int) -> float:
    want = np.argsort(-ref, axis=1)[:, :k]
    got = [len(set(hit_ids(r)) & set(w.tolist())) / k
           for r, w in zip(resps, want)]
    return float(np.mean(got))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def one_chip(srv: Served, corpus: Corpus, fails: Failures, device: str):
    segs = load(srv, corpus, "docs", 1)
    cat = srv.ok("GET", "/_cat/segments?format=json")
    sizes = [int(r["docs.count"]) for r in cat if r["index"] == "docs"]
    fails.check(sizes == [hi - lo for lo, hi in segs],
                f"segments {sizes}, want {[hi - lo for lo, hi in segs]}")
    q = Queries(corpus, SEED + 1)
    qs = range(N_QUERIES)
    bm25 = run_class(srv, fails, device, "bm25", "docs",
                     [{**UNCACHED, "query": q.match(i)} for i in qs],
                     "batch", (("bm25", "batch"),))
    knn_r = run_class(srv, fails, device, "knn", "docs",
                      [{**UNCACHED, "query": {"knn": q.knn(i)}} for i in qs],
                      "batch", (("knn", "batch"),))
    fknn = run_class(srv, fails, device, "knn_filtered", "docs",
                     [{**UNCACHED, "query": {"knn": q.knn(i, True)}}
                      for i in qs], "batch", (("knn", "batch"),))
    hybrid = run_class(srv, fails, device, "hybrid", "docs",
                       [{**UNCACHED, "query": q.match(i), "knn": q.knn(i),
                         "rank": {"rrf": {}}} for i in qs],
                       "batch", (("bm25", "batch"), ("knn", "batch")))
    sparse = run_class(srv, fails, device, "sparse", "docs",
                       [{**UNCACHED, "query": q.sparse(i)} for i in qs],
                       "batch", (("sparse", "batch"),))
    aggs = run_class(srv, fails, device, "terms_agg", "docs",
                     [{"size": 0, "request_cache": False, "query": q.match(i),
                       "aggs": {"tags": {"terms": {"field": "tag",
                                                   "size": TAGS}}}}
                      for i in qs],
                     "dense_device", (("bm25", "dense_device"),))

    # -- against the plain references ------------------------------------
    t0 = time.perf_counter()
    for qi, resp in enumerate(bm25):
        if resp is None:
            continue
        why = topk_matches(hit_ids(resp), hit_scores(resp),
                           bm25_oracle(corpus, segs, q.terms[qi]), K)
        fails.check(not why, f"bm25 query {qi} vs numpy BM25: {why}")
    cos = cosine_oracle(corpus, q.vecs)
    if all(knn_r):
        r = recall(knn_r, cos, K)
        log(f"knn: recall@{K} vs numpy exact f32 = {r:.4f}")
        fails.check(r >= 0.95, f"knn recall@{K} {r:.4f} < 0.95")
    if all(fknn):
        ref = np.stack([np.where(corpus.tags == q.tags[i], cos[i], -np.inf)
                        for i in range(N_QUERIES)])
        r = recall(fknn, ref, K)
        log(f"knn_filtered: recall@{K} vs numpy exact f32 = {r:.4f}")
        fails.check(r >= 0.95, f"knn_filtered recall@{K} {r:.4f} < 0.95")
        fails.check(all(corpus.tags[d] == q.tags[i]
                        for i, resp in enumerate(fknn)
                        for d in hit_ids(resp)),
                    "knn_filtered: a hit outside its filter")
    for qi, resp in enumerate(sparse):
        if resp is None:
            continue
        why = topk_matches(hit_ids(resp), hit_scores(resp),
                           sparse_oracle(corpus, q.feats[qi]), K)
        fails.check(not why, f"sparse query {qi} vs numpy: {why}")
    for qi, resp in enumerate(hybrid):
        if resp is None or bm25[qi] is None or knn_r[qi] is None:
            continue
        fused = {}
        for leg in (bm25[qi], knn_r[qi]):
            for rank, d in enumerate(hit_ids(leg), start=1):
                fused[d] = fused.get(d, 0.0) + 1.0 / (60 + rank)
        want = sorted(fused, key=lambda d: (-fused[d], str(d)))[:K]
        fails.check(hit_ids(resp) == want,
                    f"hybrid query {qi}: {hit_ids(resp)} != RRF of its "
                    f"legs {want}")
    for qi, resp in enumerate(aggs):
        if resp is None:
            continue
        matched = np.zeros(corpus.n, bool)
        for t in q.terms[qi]:
            matched[corpus.doc_of[corpus.tokens == t]] = True
        counts = np.bincount(corpus.tags[matched], minlength=TAGS)
        want = sorted(((f"t{t}", int(c)) for t, c in enumerate(counts) if c),
                      key=lambda e: (-e[1], e[0]))
        got = [(b["key"], b["doc_count"])
               for b in resp["aggregations"]["tags"]["buckets"]]
        fails.check(got == want, f"terms_agg query {qi}: {got[:4]}... != "
                                 f"numpy {want[:4]}...")
        fails.check(resp["hits"]["total"]["value"] == min(
            int(matched.sum()), 10000), f"terms_agg query {qi}: total")
    log(f"references: compared in {time.perf_counter() - t0:.1f}s")

    # -- a concurrent kNN wave equals the same queries solo ---------------
    before = srv.stats()["search_batch"]
    wave_bodies = [{**UNCACHED, "query": {"knn": q.knn(i)}} for i in range(4)]
    lines = "".join(json.dumps({"index": "docs"}) + "\n" + json.dumps(b)
                    + "\n" for b in wave_bodies)
    wave = srv.ok("POST", "/_msearch", lines, ndjson=True)["responses"]
    after = srv.stats()["search_batch"]
    log(f"knn wave: {len(wave)} queries, batcher max occupancy "
        f"{after.get('max_occupancy')}, batches "
        f"+{after['batches_dispatched'] - before['batches_dispatched']} for "
        f"+{after['queries_dispatched'] - before['queries_dispatched']} "
        f"queries")
    fails.check(after["queries_dispatched"] - before["queries_dispatched"]
                > after["batches_dispatched"] - before["batches_dispatched"],
                "knn wave did not coalesce into a batch")
    for body, w in zip(wave_bodies, wave):
        solo = srv.ok("POST", "/docs/_search", body)
        fails.check(json.dumps(w["hits"]["hits"]) ==
                    json.dumps(solo["hits"]["hits"]),
                    "knn wave hits differ from the same query solo")

    plane = srv.stats()["device_plane"]
    res = plane.get("resident_bytes", {})
    log(f"device plane: {plane.get('planes_resident')} planes resident, "
        f"bytes {res}, quantized queries {plane.get('quantized_queries')}")
    for kind in ("postings", "vectors", "features"):
        fails.check(res.get(kind, 0) > 0, f"no resident {kind} plane")


def four_chips(srv: Served, corpus: Corpus, fails: Failures, device: str):
    import jax

    from elasticsearch_tpu.ops.device_segment import MESH_PLANES
    load(srv, corpus, "mesh4", 4, MESH_REFRESHES)
    cat = srv.ok("GET", "/_cat/segments?format=json")
    sizes = sorted(int(r["docs.count"]) for r in cat if r["index"] == "mesh4")
    log(f"mesh4 segments: {sizes}")
    q = Queries(corpus, SEED + 2)
    classes = {name: [{**UNCACHED, "query": make(i)}
                      for i in range(N_QUERIES)]
               for name, make in (("bm25", q.match),
                                  ("knn", lambda i: {"knn": q.knn(i)}),
                                  ("sparse", q.sparse))}
    # the mesh drain's trace carries the device dispatches ("mesh"),
    # the coordinator's the routing verdict ("mesh_plane")
    mesh = {name: run_class(srv, fails, device, f"mesh {name}", "mesh4",
                            bodies, "mesh_plane",
                            ((name, "mesh"), (name, "mesh_plane")))
            for name, bodies in classes.items()}

    parts = list(MESH_PLANES._parts.values())
    fails.check(len(parts) >= 3, f"{len(parts)} mesh planes resident")
    for part in parts:
        devs = set(part.mesh.devices.flat)
        arr = getattr(part, "matrix", None)
        if arr is None:
            arr = part.block_docs
        held = {s.device for s in arr.addressable_shards if s.data.size}
        log(f"mesh plane {part.kind}/{part.field}: mesh {part.mesh.shape}, "
            f"{len(held)} devices hold slots, "
            f"{part.per_device_bytes} bytes per device")
        fails.check(len(devs) == 4 and len(held) == 4,
                    f"mesh plane {part.kind} spans {len(devs)} devices, "
                    f"slots on {len(held)}")
    for d in jax.devices()[:4]:
        stats = d.memory_stats()
        if stats is None:          # the CPU rehearsal's devices
            continue
        used = stats.get("bytes_in_use", 0)
        log(f"device {d.id} ({d.device_kind}): {used} bytes in use")
        fails.check(used > 0, f"device {d.id} holds nothing")

    srv.ok("PUT", "/_cluster/settings",
           {"persistent": {"search.mesh.enabled": False}})
    try:
        for name, bodies in classes.items():
            rpc = run_class(srv, fails, device, f"rpc {name}", "mesh4",
                            bodies, "batch", ((name, "batch"),))
            same_scores = True
            for qi, (m, r) in enumerate(zip(mesh[name], rpc)):
                if m is None or r is None:
                    continue
                fails.check(hit_ids(m) == hit_ids(r),
                            f"{name} query {qi}: mesh {hit_ids(m)} != "
                            f"rpc {hit_ids(r)}")
                same_scores &= hit_scores(m) == hit_scores(r)
            log(f"{name}: mesh hit ids == rpc hit ids checked, scores "
                f"{'identical' if same_scores else 'differ'}")
    finally:
        srv.ok("PUT", "/_cluster/settings",
               {"persistent": {"search.mesh.enabled": None}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve a 4-shard index from the mesh program "
                         "over 4 chips and compare with the RPC fan-out")
    ap.add_argument("--docs", type=int, default=None,
                    help=f"corpus size (default {ONE_CHIP_DOCS}, "
                         f"--four-chips {FULL_DOCS}; a cut below 2^20 "
                         f"prints 'reduced')")
    args = ap.parse_args(argv)
    if args.docs is None:
        args.docs = FULL_DOCS if args.four_chips else ONE_CHIP_DOCS

    here = Path(__file__).resolve().parent
    try:
        import elasticsearch_tpu
    except ImportError as e:
        print(f"chip_smoke: the package is not beside this script: {e}",
              file=sys.stderr)
        return 2
    if Path(elasticsearch_tpu.__file__).resolve().parent.parent != here:
        print("chip_smoke: elasticsearch_tpu is not this checkout's",
              file=sys.stderr)
        return 2
    from elasticsearch_tpu.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: {len(devices)} device(s), need {want}",
              file=sys.stderr)
        return 1
    device = f"{platform}/{kind} x{len(devices)}"
    from elasticsearch_tpu import native
    log(f"device: {device}; compile cache {cache}; native helper "
        f"{'loaded' if native.available() else 'NOT loaded'}")
    if args.docs < FULL_DOCS:
        log(f"reduced: corpus {args.docs} docs of {FULL_DOCS}")

    t0 = time.perf_counter()
    corpus = Corpus(args.docs)
    log(f"corpus: {args.docs} docs, {corpus.tokens.size} tokens generated "
        f"in {time.perf_counter() - t0:.1f}s")
    fails = Failures()
    srv = Served()
    try:
        (four_chips if args.four_chips else one_chip)(
            srv, corpus, fails, device)
    finally:
        srv.close()
    log(f"wall: {time.perf_counter() - t0:.1f}s")
    if fails.items:
        print(f"chip_smoke: {len(fails.items)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
