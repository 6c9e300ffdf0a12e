"""Mesh data plane: the SPMD one-program search path behind the node.

When one node process drives a multi-device mesh (a TPU slice) and holds
every active shard of an index locally, eligible whole-index top-k queries
skip the per-shard RPC fan-out entirely: the corpus lives sharded over the
mesh and the query runs as ONE pjit program — local score -> local top-k ->
all_gather merge (parallel/sharded_search.py). This collapses the
reference's scatter-gather (action/search/AbstractSearchAsyncAction.java:156
fan-out + SearchPhaseController.java:160 merge) into compiled collectives
over ICI, per SURVEY §5.8's two-plane design; the host RPC path remains the
fallback for everything else (multi-node topologies, aggs, filters, exact
counts).

The mesh copy is rebuilt lazily per (index, field) whenever the underlying
shard readers change (segment set or live-doc count), and is born merged:
tombstoned docs are dropped at build time, so totals/idf reflect live docs
only — the same scores the RPC path produces after a force-merge.
"""

from __future__ import annotations

import time

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.search import dsl

__all__ = ["MeshDataPlane", "mesh_eligible"]


def mesh_eligible(body: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Describe how the request can run as one mesh program, or None.

    Returns {"kind": "text", "field", "clauses"} for disjunctive text
    queries (Match, or bool of only-should Matches on one field — the
    same shapes the shard WAND collector serves),
    {"kind": "knn", "field", "query"} for unfiltered kNN queries, or
    {"kind": "sparse", "field", "query"} for text_expansion /
    rank-features queries. Structural conditions mirror
    choose_collector_context plus mesh-specific ones (no phases needing
    per-shard readers during query).

    Totals: text serves totals-disabled AND finite-threshold requests
    (counts-then-skip rides the sharded program's psum'd match counts);
    only track_total_hits: true (unbounded exact) falls back to RPC.
    knn/sparse are top-k-exact by construction (total = k, relation eq),
    so any finite threshold is servable."""
    if body.get("aggs") or body.get("aggregations") or body.get("suggest"):
        return None
    if body.get("sort") is not None or body.get("search_after") is not None:
        return None
    if body.get("min_score") is not None:
        return None
    if body.get("rescore") or body.get("collapse") or body.get("slice"):
        return None
    if int(body.get("size", 10)) <= 0:
        return None
    try:
        q = dsl.parse_query(body.get("query"))
    except Exception:  # noqa: BLE001 — let the RPC path raise the real error
        return None
    if body.get("track_total_hits") is True:
        return None   # unbounded exact counting: host path
    if isinstance(q, dsl.Knn) and q.filter is None:
        return {"kind": "knn", "field": q.field, "query": q}
    if isinstance(q, dsl.TextExpansion):
        return {"kind": "sparse", "field": q.field, "query": q}
    got = dsl.disjunctive_clauses(q)
    if got is None:
        return None
    field, clauses = got
    return {"kind": "text", "field": field, "clauses": clauses}


class MeshDataPlane:
    """Owns the device mesh and per-index mesh-resident search structures."""

    def __init__(self, mesh=None, min_devices: int = 2):
        self._mesh = mesh
        self._min_devices = min_devices
        self._tried_default = False
        # (index, field) -> (freshness_key, Sharded*Index, id_map arrays)
        self._text: Dict[Tuple[str, str], Tuple[Any, Any, Any]] = {}
        self._vec: Dict[Tuple[str, str], Tuple[Any, Any, Any]] = {}
        self._feat: Dict[Tuple[str, str], Tuple[Any, Any, Any]] = {}
        self._mesh2d = None
        self.stats: Dict[str, Any] = {
            "mesh_queries": 0, "mesh_builds": 0,
            # eligible queries that escaped to the host-RPC plane because
            # the mesh program raised mid-flight (degradation telemetry)
            "mesh_fallbacks": 0,
            "wand_blocks_total": 0, "wand_blocks_scored": 0,
            # rebuild cost telemetry (VERDICT r3 weak #8: refresh-heavy
            # workloads invalidate the mesh copy — the price must be
            # observable): cumulative + last build wall seconds and docs
            "build_seconds_total": 0.0, "last_build_seconds": 0.0,
            "last_build_docs": 0}

    def _record_build(self, t0: float, n_docs: int) -> None:
        took = time.perf_counter() - t0
        self.stats["mesh_builds"] += 1
        self.stats["build_seconds_total"] = round(
            self.stats["build_seconds_total"] + took, 6)
        self.stats["last_build_seconds"] = round(took, 6)
        self.stats["last_build_docs"] = n_docs

    # ------------------------------------------------------------------

    @property
    def mesh(self):
        if self._mesh is None and not self._tried_default:
            import jax
            from jax.sharding import Mesh
            self._tried_default = True
            devices = jax.devices()
            if len(devices) >= self._min_devices:
                self._mesh = Mesh(np.array(devices), ("shard",))
        return self._mesh

    @property
    def available(self) -> bool:
        return self.mesh is not None

    @property
    def mesh2d(self):
        """(shard, dp=1) view over the same devices — the vector program's
        expected axes (queries ride the dp axis)."""
        if self._mesh2d is None and self.mesh is not None:
            import numpy as _np
            from jax.sharding import Mesh
            self._mesh2d = Mesh(
                _np.asarray(self.mesh.devices).reshape(-1, 1),
                ("shard", "dp"))
        return self._mesh2d

    # ------------------------------------------------------------------
    # build / cache
    # ------------------------------------------------------------------

    @staticmethod
    def _freshness_key(readers) -> Tuple:
        # identity of the segment set + live count per segment: any refresh,
        # merge, or delete changes it and invalidates the mesh copy
        return tuple(
            (sid, tuple(seg.uid for seg in reader.segments),
             int(sum(int(np.asarray(m).sum()) for m in reader.live_masks)))
            for sid, reader in readers)

    def _text_index(self, index_name: str, field: str, readers):
        key = self._freshness_key(readers)
        got = self._text.get((index_name, field))
        if got is not None and got[0] == key:
            return got[1], got[2]
        t0 = time.perf_counter()
        from elasticsearch_tpu.parallel.sharded_search import ShardedTextIndex
        sources = []
        id_shard: List[int] = []
        id_segment: List[int] = []
        id_doc: List[int] = []
        for sid, reader in readers:
            for si, (seg, live) in enumerate(
                    zip(reader.segments, reader.live_masks)):
                sources.append((seg.postings.get(field), live, seg.n_docs))
                id_shard.extend([sid] * seg.n_docs)
                id_segment.extend([si] * seg.n_docs)
                id_doc.extend(range(seg.n_docs))
        tindex = ShardedTextIndex.from_postings_sources(self.mesh, sources)
        id_map = (np.asarray(id_shard, np.int32),
                  np.asarray(id_segment, np.int32),
                  np.asarray(id_doc, np.int32))
        self._text[(index_name, field)] = (key, tindex, id_map)
        self._record_build(t0, tindex.n_docs)
        return tindex, id_map

    # ------------------------------------------------------------------
    # search
    # ------------------------------------------------------------------

    def _vector_index(self, index_name: str, field: str, readers):
        key = self._freshness_key(readers)
        got = self._vec.get((index_name, field))
        if got is not None and got[0] == key:
            return got[1], got[2], got[3]
        t0 = time.perf_counter()
        from elasticsearch_tpu.parallel.sharded_search import (
            ShardedVectorIndex,
        )
        rows = []
        id_shard: List[int] = []
        id_segment: List[int] = []
        id_doc: List[int] = []
        similarity = "cosine"
        for sid, reader in readers:
            for si, (seg, live) in enumerate(
                    zip(reader.segments, reader.live_masks)):
                vf = seg.vectors.get(field)
                if vf is None:
                    continue
                similarity = vf.similarity
                live = np.asarray(live[: seg.n_docs], bool)
                keep = np.nonzero(vf.exists[: seg.n_docs] & live)[0]
                if len(keep) == 0:
                    continue
                rows.append(vf.matrix[keep])
                id_shard.extend([sid] * len(keep))
                id_segment.extend([si] * len(keep))
                id_doc.extend(keep.tolist())
        if not rows:
            return None, None, None
        matrix = np.concatenate(rows).astype(np.float32)
        vindex = ShardedVectorIndex(self.mesh2d, matrix,
                                    similarity=similarity)
        id_map = (np.asarray(id_shard, np.int32),
                  np.asarray(id_segment, np.int32),
                  np.asarray(id_doc, np.int32))
        # per-shard live-vector counts, computed ONCE per build: knn
        # totals parity needs them every query and id_map scans are
        # O(n_docs)
        _, shard_counts = np.unique(id_map[0], return_counts=True)
        self._vec[(index_name, field)] = (key, vindex, id_map,
                                          shard_counts)
        self._record_build(t0, vindex.n_docs)
        return vindex, id_map, shard_counts

    def _features_index(self, index_name: str, field: str, readers):
        key = self._freshness_key(readers)
        got = self._feat.get((index_name, field))
        if got is not None and got[0] == key:
            return got[1], got[2]
        t0 = time.perf_counter()
        from elasticsearch_tpu.parallel.sharded_search import (
            ShardedFeaturesIndex,
        )
        sources = []
        id_shard: List[int] = []
        id_segment: List[int] = []
        id_doc: List[int] = []
        for sid, reader in readers:
            for si, (seg, live) in enumerate(
                    zip(reader.segments, reader.live_masks)):
                sources.append((seg.features.get(field), live, seg.n_docs))
                id_shard.extend([sid] * seg.n_docs)
                id_segment.extend([si] * seg.n_docs)
                id_doc.extend(range(seg.n_docs))
        findex = ShardedFeaturesIndex.from_features_sources(self.mesh,
                                                            sources)
        id_map = (np.asarray(id_shard, np.int32),
                  np.asarray(id_segment, np.int32),
                  np.asarray(id_doc, np.int32))
        self._feat[(index_name, field)] = (key, findex, id_map)
        self._record_build(t0, findex.n_docs)
        return findex, id_map

    # ------------------------------------------------------------------
    # search
    # ------------------------------------------------------------------

    @staticmethod
    def _want(body: Dict[str, Any], n: int) -> int:
        want = int(body.get("size", 10)) + int(body.get("from", 0))
        return max(1, min(want, n if n else 1))

    def _emit(self, scores, ids, id_map, boost: float
              ) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        for sc, gid in zip(np.asarray(scores), np.asarray(ids)):
            if not np.isfinite(sc) or gid < 0:
                break
            out.append({"shard": int(id_map[0][gid]),
                        "segment": int(id_map[1][gid]),
                        "doc": int(id_map[2][gid]),
                        "score": float(sc) * boost,
                        "sort": [float(sc) * boost]})
        return out

    def search_text(self, index_name: str, field: str, shards,
                    body: Dict[str, Any], mappers,
                    clauses=None) -> Optional[Dict[str, Any]]:
        """Run the one-program path; returns {"hits": [...], "total",
        "relation"} with hits globally sorted, or None if the field isn't
        analyzable here (caller falls back to RPC).

        Totals (counts-then-skip over the mesh): with a finite
        track_total_hits the counted program observes matches in the
        blocks it gathers — observed >= threshold proves ("gte",
        threshold); otherwise an unpruned counted pass gives the exact
        count (skipped entirely when the df upper bound already fits
        under the threshold)."""
        if not self.available:
            return None
        mapper = mappers.mapper(field)
        analyzer = getattr(mapper, "search_analyzer", None)
        if analyzer is None:
            return None
        if clauses is None:
            q = dsl.parse_query(body.get("query"))
            clauses = [(q.text, q.boost)]
        terms: List[Any] = []
        for text, boost in clauses:
            terms.extend((t, float(boost)) for t in analyzer.terms(text))
        if not terms:
            return {"hits": [], "total": 0, "relation": "eq"}
        tth = body.get("track_total_hits", 10_000)
        limit = 0 if tth in (False, 0) else int(tth)
        readers = [(sid, shard.engine.acquire_reader())
                   for sid, shard in sorted(shards.items())]
        tindex, id_map = self._text_index(index_name, field, readers)
        k = self._want(body, tindex.n_docs)
        count = limit > 0
        if count and tindex.hits_upper(terms) <= limit:
            # few enough postings that pruning can't pay: one unpruned
            # counted pass, exact totals for free
            scores, ids, hits = tindex.search_batch(
                [terms], k, prune=False, count_hits=True)
            total, relation = int(hits[0]), "eq"
        elif count:
            scores, ids, hits = tindex.search_batch(
                [terms], k, count_hits=True)
            observed = int(hits[0])
            if observed >= limit:
                total, relation = limit, "gte"
            elif tindex.last_hits_exact:
                total, relation = observed, "eq"
            else:
                _s, _i, hits = tindex.search_batch(
                    [terms], 1, prune=False, count_hits=True)
                exact = int(hits[0])
                total, relation = (limit, "gte") if exact > limit \
                    else (exact, "eq")
        else:
            scores, ids = tindex.search_batch([terms], k)
            total, relation = None, "gte"
        t, g = tindex.last_prune_stats
        self.stats["mesh_queries"] += 1
        self.stats["wand_blocks_total"] += t
        self.stats["wand_blocks_scored"] += g
        out = self._emit(scores[0], ids[0], id_map, 1.0)
        return {"hits": out,
                "total": len(out) if total is None else total,
                "relation": relation}

    def search_knn(self, index_name: str, field: str, shards,
                   body: Dict[str, Any], query: "dsl.Knn"
                   ) -> Optional[Dict[str, Any]]:
        """Unfiltered exact kNN as one mesh program (the
        parallel/sharded_search.py kNN kernel behind the REST surface)."""
        if not self.available:
            return None
        readers = [(sid, shard.engine.acquire_reader())
                   for sid, shard in sorted(shards.items())]
        vindex, id_map, shard_counts = self._vector_index(
            index_name, field, readers)
        if vindex is None:
            return None
        # size+from bounds the result like the RPC path's shard collection
        # window (query.k bounds PER-SHARD collection there, so clamping
        # the global mesh result by it would return fewer hits than the
        # RPC path on multi-shard indices)
        k = self._want(body, vindex.n_docs)
        qv = np.asarray(query.query_vector, np.float32)[None, :]
        scores, ids = vindex.search(qv, k)
        self.stats["mesh_queries"] += 1
        out = self._emit(scores[0], ids[0], id_map, query.boost)
        # totals match the RPC plane's EXACT path: there each shard's Knn
        # rewrites to a per-shard top-k doc set (KnnBound, <= query.k
        # docs) and the coordinator sums per-shard collection counts.
        # Documented divergence: the RPC ANN path (ivf opt-in or
        # >=65536-doc segments) can post-filter to fewer than k live
        # hits; the mesh plane is always exact, so it reports the exact
        # path's total. The hit window (size+from) is not bounded by
        # query.k, so the clamp keeps hits <= total invariant when the
        # window exceeds the per-shard collection sum (ADVICE r5 medium).
        total = max(int(np.minimum(shard_counts, query.k).sum()), len(out))
        return {"hits": out, "total": total, "relation": "eq"}

    def search_sparse(self, index_name: str, field: str, shards,
                      body: Dict[str, Any], query: "dsl.TextExpansion"
                      ) -> Optional[Dict[str, Any]]:
        """text_expansion / learned-sparse retrieval as one mesh program:
        expansion tokens (from the on-device model when not precomputed)
        score linearly against the sharded rank-features blocks."""
        if not self.available:
            return None
        tokens = query.tokens
        if tokens is None:
            from elasticsearch_tpu.ml import get_model
            tokens = get_model(query.model_id).expand(query.model_text or "")
        if not tokens:
            return {"hits": [], "total": 0, "relation": "eq"}
        readers = [(sid, shard.engine.acquire_reader())
                   for sid, shard in sorted(shards.items())]
        findex, id_map = self._features_index(index_name, field, readers)
        if findex is None or findex.n_docs == 0:
            return {"hits": [], "total": 0, "relation": "eq"}
        k = self._want(body, findex.n_docs)
        expansion = [(t, float(w) * query.boost) for t, w in tokens.items()]
        scores, ids = findex.search_batch([expansion], k)
        self.stats["mesh_queries"] += 1
        out = self._emit(scores[0], ids[0], id_map, 1.0)
        # the sparse mesh program returns only the global top-k, so the
        # matched-doc count is unobserved; len(out) is a LOWER bound —
        # report "gte" rather than claiming the RPC plane's exact
        # collected-count (documented divergence, vs search_text's
        # counts-then-skip which does prove its totals)
        return {"hits": out, "total": len(out), "relation": "gte"}
