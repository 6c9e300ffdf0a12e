"""SPMD distributed search: one pjit program replaces scatter-gather RPC.

The reference fans a query out over shards with per-shard RPCs and merges
top-k on a coordinator (action/search/AbstractSearchAsyncAction.java:156,214;
SearchPhaseController.sortDocs:160 + TopDocs.merge). Here the whole
scatter-gather is ONE compiled program over a (dp, shard) mesh:

  local score -> local top-k -> all_gather(top-k over 'shard') -> global top-k

The all_gather moves only k (score, id) pairs per shard — the wire-efficient
merge the reference gets from query_then_fetch — but over ICI inside the
compiled program instead of TCP between processes. DFS-style global term
stats (search/dfs/DfsPhase.java:43) become a host-side df sum (or a psum)
before weight computation.

Layouts (S = number of shards on the mesh axis):
  postings: block_docs/tfs [S, NB, BLOCK] sharded on axis 0; local doc ids
  vectors:  matrix [S, N, D] sharded on axis 0
  queries:  [B, ...] sharded on 'dp'
Docs are placed round-robin (doc g -> shard g % S, local g // S) so load
balances regardless of pow2 padding (the murmur3-routing analog for a
monotonically-assigned corpus). Inside a program a doc is addressed by its
mesh-global id shard_idx * N_per_shard + local. The batched BM25 program
emits ORIGINAL corpus ids directly (tie-break by ascending original id is
baked into its device-side lexsort); the single-query and kNN paths still
return mesh-global ids that search APIs translate via to_original_ids.
"""

from __future__ import annotations

from collections import Counter
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.index.segment import BLOCK, next_pow2
from elasticsearch_tpu.search.device_profile import profiled_callable
from elasticsearch_tpu.ops.bm25 import (
    DEFAULT_B, DEFAULT_K1, P1_BUCKET, QueryPlan, TermCellIndex,
    build_query_plan, idf as idf_fn, qb_bucket,
)


# ---------------------------------------------------------------------------
# shared local scoring bodies (used by the standalone AND hybrid programs —
# one definition so kernel fixes can't drift between them)
# ---------------------------------------------------------------------------

def _local_bm25_scores(block_docs, block_tfs, doc_lens, avgdl,
                       block_idx, block_w, n_per_shard: int,
                       k1: float, b: float):
    """Per-shard BM25: gather query blocks, score, scatter-add into doc
    space. Returns dense scores [n_per_shard] with -inf for non-matches."""
    docs = block_docs[block_idx]              # [QB, BLOCK]
    tfs = block_tfs[block_idx]
    valid = docs >= 0
    safe = jnp.where(valid, docs, 0)
    dl = doc_lens[safe]
    norm = k1 * (1.0 - b + b * dl / avgdl)
    contrib = block_w[:, None] * tfs * (k1 + 1.0) / (tfs + norm)
    contrib = jnp.where(valid, contrib, 0.0)
    scores = jnp.zeros((n_per_shard,), jnp.float32)
    scores = scores.at[safe.reshape(-1)].add(contrib.reshape(-1), mode="drop")
    return jnp.where(scores > 0, scores, -jnp.inf)


def _local_knn_scores(m, norms, valid, queries, similarity: str):
    """Per-shard kNN: MXU matmul + similarity transform.
    queries [B, D] -> scores [B, N] with -inf for missing vectors.

    cosine/dot run in bf16 (the dot IS the score — bf16 relative error is
    fine). l2 runs the dot in f32: the ||m||^2 + ||q||^2 - 2<q,m>
    cancellation turns bf16 rounding into large absolute error exactly at
    small distances, where ranking is decided.
    """
    dot_dtype = jnp.float32 if similarity == "l2_norm" else jnp.bfloat16
    dots = jax.lax.dot_general(
        queries.astype(dot_dtype), m.astype(dot_dtype),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [B, N]
    if similarity == "cosine":
        qn = jnp.linalg.norm(queries, axis=1, keepdims=True) + 1e-30
        scores = (1.0 + dots / (norms[None, :] * qn + 1e-30)) / 2.0
    elif similarity == "dot_product":
        scores = 0.5 + dots / 2.0
    elif similarity == "l2_norm":
        q2 = jnp.sum(queries * queries, axis=1, keepdims=True)
        d2 = jnp.maximum(norms[None, :] ** 2 + q2 - 2.0 * dots, 0.0)
        scores = 1.0 / (1.0 + jnp.sqrt(d2))
    else:
        raise ValueError(f"unknown similarity {similarity!r}")
    return jnp.where(valid[None, :], scores, -jnp.inf)


def to_original_ids(ids, n_shards: int, n_per_shard: int):
    """Mesh-global ids (shard*per + local) -> original corpus ids under the
    round-robin placement; -1 (empty slot) passes through."""
    ids = np.asarray(ids)
    return np.where(ids >= 0,
                    (ids % n_per_shard) * n_shards + ids // n_per_shard,
                    -1)


def _topk_padded(scores, k: int):
    """top_k that clamps to the axis size and pads back out to k with
    (-inf, -1) — ES clamps size to available hits instead of erroring."""
    n = scores.shape[-1]
    kk = min(k, n)
    s, i = jax.lax.top_k(scores, kk)
    if kk < k:
        pad = [(0, 0)] * (scores.ndim - 1) + [(0, k - kk)]
        s = jnp.pad(s, pad, constant_values=-jnp.inf)
        i = jnp.pad(i, pad, constant_values=-1)
    return s, i


def _global_topk_1d(scores, k: int, n_per_shard: int):
    """Per-shard [N] scores -> global (scores [k], ids [k]) via all_gather
    over 'shard'. Ids of -inf slots are masked to -1 so downstream fusion
    can't credit phantom/padding docs."""
    ls, li = _topk_padded(scores, k)
    shard_idx = jax.lax.axis_index("shard")
    gi = jnp.where(jnp.isfinite(ls), li + shard_idx * n_per_shard, -1)
    all_s = jax.lax.all_gather(ls, "shard", axis=0).reshape(-1)
    all_i = jax.lax.all_gather(gi, "shard", axis=0).reshape(-1)
    gs, pos = jax.lax.top_k(all_s, k)
    return gs, all_i[pos]


# ---------------------------------------------------------------------------
# sharded kNN
# ---------------------------------------------------------------------------

def make_sharded_knn(mesh: Mesh, n_per_shard: int, dims: int, k: int,
                     similarity: str = "cosine"):
    """Compile the distributed kNN program for the given shapes.

    Returns fn(matrix [S,N,D], norms [S,N], valid [S,N], queries [B,D])
    -> (scores [B,k], global_ids [B,k]).
    """

    def local_search(matrix, norms, valid, queries):
        # per-device blocks: matrix [1, N, D], queries [B_local, D]
        scores = _local_knn_scores(matrix[0], norms[0], valid[0], queries,
                                   similarity)
        local_s, local_i = _topk_padded(scores, k)          # [B, k]
        shard_idx = jax.lax.axis_index("shard")
        global_i = jnp.where(jnp.isfinite(local_s),
                             local_i + shard_idx * n_per_shard, -1)
        # gather each shard's top-k, then reduce to the global top-k
        all_s = jax.lax.all_gather(local_s, "shard", axis=0)   # [S, B, k]
        all_i = jax.lax.all_gather(global_i, "shard", axis=0)
        S = all_s.shape[0]
        B = all_s.shape[1]
        flat_s = jnp.transpose(all_s, (1, 0, 2)).reshape(B, S * k)
        flat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(B, S * k)
        g_s, pos = jax.lax.top_k(flat_s, k)
        g_i = jnp.take_along_axis(flat_i, pos, axis=1)
        return g_s, g_i

    fn = shard_map(
        local_search, mesh=mesh,
        in_specs=(P("shard", None, None), P("shard", None), P("shard", None),
                  P("dp", None)),
        out_specs=(P("dp", None), P("dp", None)),
        check_vma=False,
    )
    return profiled_callable("sharded_knn", fn)


class ShardedVectorIndex:
    """Corpus of vectors partitioned over the mesh 'shard' axis."""

    def __init__(self, mesh: Mesh, vectors: np.ndarray,
                 similarity: str = "cosine",
                 n_per_shard: Optional[int] = None):
        self.mesh = mesh
        n_shards = mesh.shape["shard"]
        n, d = vectors.shape
        self.n_docs = n
        per = n_per_shard or next_pow2(max(-(-n // n_shards), 1), minimum=8)
        self.n_per_shard = per
        mat = np.zeros((n_shards, per, d), np.float32)
        valid = np.zeros((n_shards, per), bool)
        for s in range(n_shards):
            orig = np.arange(s, n, n_shards)     # round-robin placement
            mat[s, : len(orig)] = vectors[orig]
            valid[s, : len(orig)] = True
        norms = np.linalg.norm(mat, axis=2).astype(np.float32)
        self.matrix = jax.device_put(mat, NamedSharding(mesh, P("shard", None, None)))
        self.norms = jax.device_put(norms, NamedSharding(mesh, P("shard", None)))
        self.valid = jax.device_put(valid, NamedSharding(mesh, P("shard", None)))
        self.similarity = similarity
        self._compiled: Dict[int, callable] = {}

    def search(self, queries: np.ndarray, k: int):
        """queries [B, D] -> (scores [B, k], global doc ids [B, k]).

        B need not divide the dp axis: the batch is padded to a multiple of
        n_dp for the sharded device_put and the pad rows dropped on return.
        """
        fn = self._compiled.get(k)
        if fn is None:
            fn = make_sharded_knn(self.mesh, self.n_per_shard,
                                  queries.shape[1], k, self.similarity)
            self._compiled[k] = fn
        b = queries.shape[0]
        n_dp = self.mesh.shape["dp"]
        b_pad = -(-b // n_dp) * n_dp
        q = np.zeros((b_pad, queries.shape[1]), np.float32)
        q[:b] = queries
        q = jax.device_put(jnp.asarray(q),
                           NamedSharding(self.mesh, P("dp", None)))
        s, i = fn(self.matrix, self.norms, self.valid, q)
        return s[:b], to_original_ids(i[:b], self.mesh.shape["shard"],
                                      self.n_per_shard)


# ---------------------------------------------------------------------------
# sharded BM25
# ---------------------------------------------------------------------------

def make_sharded_bm25(mesh: Mesh, n_per_shard: int, k: int,
                      k1: float = DEFAULT_K1, b: float = DEFAULT_B):
    """Compile the distributed BM25 program.

    fn(block_docs [S,NB,BLOCK], block_tfs [S,NB,BLOCK], doc_lens [S,N],
       avgdl scalar, block_idx [S,QB], block_w [S,QB])
    -> (scores [k], global ids [k])  (single query; batch via host loop or vmap)
    """

    def local_search(block_docs, block_tfs, doc_lens, avgdl, block_idx, block_w):
        scores = _local_bm25_scores(block_docs[0], block_tfs[0], doc_lens[0],
                                    avgdl, block_idx[0], block_w[0],
                                    n_per_shard, k1, b)
        return _global_topk_1d(scores, k, n_per_shard)

    fn = shard_map(
        local_search, mesh=mesh,
        in_specs=(P("shard", None, None), P("shard", None, None),
                  P("shard", None), P(), P("shard", None), P("shard", None)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return profiled_callable("sharded_bm25", fn)


def make_sharded_bm25_batch(mesh: Mesh, n_per_shard: int, k: int,
                            k1: float = DEFAULT_K1, b: float = DEFAULT_B,
                            counted: bool = False):
    """Compile the BATCHED distributed BM25 program: Q queries per dispatch
    (the knn batched-program analog — BM25 was previously dispatch-bound at
    one compiled call per query).

    fn(block_docs [S,NB,BLOCK], block_tfs [S,NB,BLOCK], doc_lens [S,N],
       avgdl scalar, block_idx [S,Q,QB], block_w [S,Q,QB])
    -> (scores [Q,k], ORIGINAL corpus doc ids [Q,k])

    Ties at equal score break by ascending original id — the same
    (shard, segment, doc) order the host-RPC coordinator merge uses
    (SearchPhaseController.java:160 analog), so both data planes return
    identical hit sets at tie boundaries.
    """

    def local_search(block_docs, block_tfs, doc_lens, avgdl,
                     block_idx, block_w):
        def one(bi, bw):
            return _local_bm25_scores(block_docs[0], block_tfs[0],
                                      doc_lens[0], avgdl, bi, bw,
                                      n_per_shard, k1, b)
        scores = jax.vmap(one)(block_idx[0], block_w[0])       # [Q, N]
        local_s, local_i = _topk_padded(scores, k)             # [Q, k]
        shard_idx = jax.lax.axis_index("shard")
        # psum(1) == axis size on every jax vintage (lax.axis_size is
        # newer than the floor this build supports)
        n_shards = jax.lax.psum(1, "shard")
        # round-robin placement: original id = local * S + shard; empty
        # slots get an out-of-range id so the lexsort puts them last
        orig_i = jnp.where(jnp.isfinite(local_s),
                           local_i * n_shards + shard_idx,
                           n_shards * n_per_shard)
        all_s = jax.lax.all_gather(local_s, "shard", axis=0)   # [S, Q, k]
        all_i = jax.lax.all_gather(orig_i, "shard", axis=0)
        S, Q = all_s.shape[0], all_s.shape[1]
        flat_s = jnp.transpose(all_s, (1, 0, 2)).reshape(Q, S * k)
        flat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(Q, S * k)
        # lexicographic (descending score, ascending original id)
        srt_neg, srt_i = jax.lax.sort((-flat_s, flat_i), dimension=1,
                                      num_keys=2)
        g_s = -srt_neg[:, :k]
        g_i = jnp.where(jnp.isfinite(g_s), srt_i[:, :k], -1)
        if counted:
            # matched docs across the mesh: local finite-score count,
            # summed over the shard axis (counts-then-skip's observation
            # — exact when every block was gathered, else a lower bound)
            local_hits = jnp.sum(jnp.isfinite(scores), axis=1,
                                 dtype=jnp.int32)               # [Q]
            hits = jax.lax.psum(local_hits, "shard")
            return g_s, g_i, hits
        return g_s, g_i

    fn = shard_map(
        local_search, mesh=mesh,
        in_specs=(P("shard", None, None), P("shard", None, None),
                  P("shard", None), P(), P("shard", None, None),
                  P("shard", None, None)),
        out_specs=(P(), P(), P()) if counted else (P(), P()),
        check_vma=False,
    )
    return profiled_callable("sharded_bm25_batch", fn)


class ShardedTextIndex:
    """Text corpus partitioned by doc over the mesh 'shard' axis, with
    corpus-GLOBAL document frequencies so every shard scores with the same
    idf.

    The reference routes docs to shards by murmur3 and each shard builds its
    own Lucene index; idf consistency comes from the optional DFS phase. Here
    per-shard dfs are summed host-side at build time for exact global idf,
    and the per-query host prep emits one gather list per shard.
    """

    def __init__(self, mesh: Mesh, docs_terms: Sequence[Sequence[str]],
                 qb_bucket_min: int = 8):
        n_shards = mesh.shape["shard"]
        n = len(docs_terms)
        per = next_pow2(max(-(-n // n_shards), 1), minimum=BLOCK)

        # per-shard postings: term -> {local_doc: tf}
        shard_postings: List[Dict[str, Dict[int, int]]] = [dict() for _ in range(n_shards)]
        doc_lens = np.zeros((n_shards, per), np.float32)
        df: Dict[str, int] = {}
        for g, terms in enumerate(docs_terms):
            s, local = g % n_shards, g // n_shards   # round-robin placement
            doc_lens[s, local] = len(terms)
            seen = set()
            for t in terms:
                shard_postings[s].setdefault(t, {})
                shard_postings[s][t][local] = shard_postings[s][t].get(local, 0) + 1
                if t not in seen:
                    df[t] = df.get(t, 0) + 1
                    seen.add(t)
        self._finish_init(mesh, n, per, shard_postings, doc_lens, df,
                          qb_bucket_min)

    @classmethod
    def from_postings_sources(cls, mesh: Mesh, sources,
                              qb_bucket_min: int = 8) -> "ShardedTextIndex":
        """Build directly from already-indexed postings instead of raw docs.

        ``sources``: ordered [(postings_field_or_None, live_mask, n_docs)]
        — one entry per source segment, concatenated into a global doc
        space (global id g = segment base + local doc). Tombstoned docs are
        dropped at build time (the mesh copy is born merged), so df and
        doc_lens reflect live docs only."""
        obj = cls.__new__(cls)
        n_shards = mesh.shape["shard"]
        n = sum(n_docs for _, _, n_docs in sources)
        per = next_pow2(max(-(-n // n_shards), 1), minimum=BLOCK)
        shard_postings: List[Dict[str, Dict[int, int]]] = \
            [dict() for _ in range(n_shards)]
        doc_lens = np.zeros((n_shards, per), np.float32)
        df: Dict[str, int] = {}
        base = 0
        for pf, live, n_docs in sources:
            if pf is None or n_docs == 0:
                base += n_docs
                continue
            live = np.asarray(live[:n_docs], bool)
            g = base + np.arange(n_docs)
            s_arr, local_arr = g % n_shards, g // n_shards
            lens = np.where(live, pf.doc_lens[:n_docs], 0.0)
            doc_lens[s_arr, local_arr] = lens
            for term in pf.terms:
                docs, tfs = pf.postings_for(term)
                keep = live[docs]
                docs, tfs = docs[keep], tfs[keep]
                if len(docs) == 0:
                    continue
                df[term] = df.get(term, 0) + len(docs)
                gg = base + docs
                for gdoc, tf in zip(gg.tolist(), tfs.tolist()):
                    sp = shard_postings[gdoc % n_shards].setdefault(term, {})
                    sp[gdoc // n_shards] = int(tf)
            base += n_docs
        obj._finish_init(mesh, n, per, shard_postings, doc_lens, df,
                         qb_bucket_min)
        return obj

    def _finish_init(self, mesh: Mesh, n: int, per: int,
                     shard_postings: List[Dict[str, Dict[int, int]]],
                     doc_lens: np.ndarray, df: Dict[str, int],
                     qb_bucket_min: int) -> None:
        self.mesh = mesh
        n_shards = mesh.shape["shard"]
        self.n_shards = n_shards
        self.n_docs = n
        self.n_per_shard = per
        self.df = df

        # pack per-shard blocks; all shards padded to the same block count
        packed = []
        for s in range(n_shards):
            blocks_d, blocks_t = [], []
            index: Dict[str, Tuple[int, int]] = {}
            for t, posting in shard_postings[s].items():
                entries = sorted(posting.items())
                nb = max(1, -(-len(entries) // BLOCK))
                index[t] = (len(blocks_d), nb)
                d = np.full(nb * BLOCK, -1, np.int32)
                f = np.zeros(nb * BLOCK, np.float32)
                d[: len(entries)] = [e[0] for e in entries]
                f[: len(entries)] = [e[1] for e in entries]
                blocks_d.extend(d.reshape(nb, BLOCK))
                blocks_t.extend(f.reshape(nb, BLOCK))
            if not blocks_d:
                blocks_d = [np.full(BLOCK, -1, np.int32).reshape(1, BLOCK)[0]]
                blocks_t = [np.zeros(BLOCK, np.float32)]
            packed.append((np.stack(blocks_d), np.stack(blocks_t), index))

        nb_max = next_pow2(max(p[0].shape[0] for p in packed))
        bd = np.full((n_shards, nb_max, BLOCK), -1, np.int32)
        bt = np.zeros((n_shards, nb_max, BLOCK), np.float32)
        self.term_index: List[Dict[str, Tuple[int, int]]] = []
        for s, (d, t, index) in enumerate(packed):
            bd[s, : d.shape[0]] = d
            bt[s, : t.shape[0]] = t
            self.term_index.append(index)

        self.block_docs = jax.device_put(bd, NamedSharding(mesh, P("shard", None, None)))
        self.block_tfs = jax.device_put(bt, NamedSharding(mesh, P("shard", None, None)))
        self.doc_lens = jax.device_put(doc_lens, NamedSharding(mesh, P("shard", None)))
        total_len = float(doc_lens.sum())
        self.avgdl = total_len / max(1, n)
        # per-shard block-max impact bounds for WAND pruning (host-side,
        # default similarity params — PostingsField.block_max_impact analog)
        self._impacts = np.zeros((n_shards, nb_max), np.float32)
        for s in range(n_shards):
            v = bd[s] >= 0
            dl = doc_lens[s][np.where(v, bd[s], 0)]
            norm = DEFAULT_K1 * (1.0 - DEFAULT_B + DEFAULT_B * dl /
                                 max(self.avgdl, 1e-9))
            x = np.where(v, bt[s] / np.maximum(bt[s] + norm, 1e-9), 0.0)
            self._impacts[s] = x.max(axis=1)
        self._cell_indexes = [
            TermCellIndex(bd[s], bt[s], doc_lens[s], self.avgdl)
            for s in range(n_shards)]
        self.qb_bucket_min = qb_bucket_min
        self._compiled: Dict[Tuple[int, int], callable] = {}
        self._compiled_batch: Dict[int, callable] = {}
        self.last_prune_stats: Tuple[int, int] = (0, 0)

    def prep_query(self, terms: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
        """Host prep: per-shard gather indices + per-block weights from
        GLOBAL df (exact idf, no DFS round needed)."""
        per_shard_idx: List[List[int]] = [[] for _ in range(self.n_shards)]
        per_shard_w: List[List[float]] = [[] for _ in range(self.n_shards)]
        # dedupe but keep ES match semantics: a repeated query term is a
        # repeated bool clause, so its weight scales with multiplicity (qtf)
        # — same scoring as Bm25Executor.query_weights on the segment path.
        # Entries may be (term, boost) pairs (bool/should clause boosts).
        counts = Counter(terms)
        for t, qtf in counts.items():
            boost = 1.0
            if isinstance(t, tuple):
                t, boost = t
            df = self.df.get(t, 0)
            if df <= 0:
                continue
            w = idf_fn(self.n_docs, df) * qtf * float(boost)
            for s in range(self.n_shards):
                entry = self.term_index[s].get(t)
                if entry is None:
                    continue
                start, count = entry
                for b_ in range(start, start + count):
                    per_shard_idx[s].append(b_)
                    per_shard_w[s].append(w)
        qb = max(max((len(x) for x in per_shard_idx), default=1), 1)
        qb_pad = next_pow2(qb, minimum=self.qb_bucket_min)
        idx = np.zeros((self.n_shards, qb_pad), np.int32)
        w = np.zeros((self.n_shards, qb_pad), np.float32)
        for s in range(self.n_shards):
            idx[s, : len(per_shard_idx[s])] = per_shard_idx[s]
            w[s, : len(per_shard_w[s])] = per_shard_w[s]
        return idx, w

    def search(self, terms: Sequence[str], k: int):
        idx, w = self.prep_query(terms)
        key = (k, idx.shape[1])
        fn = self._compiled.get(key)
        if fn is None:
            fn = make_sharded_bm25(self.mesh, self.n_per_shard, k)
            self._compiled[key] = fn
        sh = NamedSharding(self.mesh, P("shard", None))
        s, i = fn(self.block_docs, self.block_tfs, self.doc_lens,
                  jnp.float32(self.avgdl),
                  jax.device_put(idx, sh), jax.device_put(w, sh))
        return s, to_original_ids(i, self.n_shards, self.n_per_shard)

    # -- batched + block-max-pruned path ------------------------------------

    def _plans(self, terms: Sequence[str]) -> List[QueryPlan]:
        """One WAND block plan per shard for one query (global idf)."""
        tw = []
        # dedupe keeping order, weight scaled by query-term multiplicity
        # (qtf) to match the repeated-bool-clause semantics of the segment
        # executor (see prep_query); entries may be (term, boost) pairs
        for t, qtf in Counter(terms).items():
            boost = 1.0
            if isinstance(t, tuple):
                t, boost = t
            df = self.df.get(t, 0)
            if df > 0:
                tw.append((t, idf_fn(self.n_docs, df) * qtf * float(boost)))
        out = []
        for s in range(self.n_shards):
            out.append(build_query_plan(
                tw, lambda t, s=s: self.term_index[s].get(t, (0, 0)),
                self._impacts[s], cell_index=self._cell_indexes[s]))
        return out

    def _batch_fn(self, k: int, counted: bool = False):
        fn = self._compiled_batch.get((k, counted))
        if fn is None:
            fn = make_sharded_bm25_batch(self.mesh, self.n_per_shard, k,
                                         counted=counted)
            self._compiled_batch[(k, counted)] = fn
        return fn

    def hits_upper(self, terms) -> int:
        """df-based upper bound on matching docs (df per distinct term;
        overlap only lowers the true union)."""
        seen = set()
        total = 0
        for t in terms:
            if isinstance(t, tuple):
                t = t[0]
            if t in seen:
                continue
            seen.add(t)
            total += int(self.df.get(t, 0))
        return total

    def _run_batch(self, fn, plans: List[List[QueryPlan]], qb_pad: int):
        """plans[q][s] -> one batched dispatch over all (query, shard)."""
        n_q = len(plans)
        idx = np.zeros((self.n_shards, n_q, qb_pad), np.int32)
        w = np.zeros((self.n_shards, n_q, qb_pad), np.float32)
        for q, per_shard in enumerate(plans):
            for s, p in enumerate(per_shard):
                idx[s, q, : p.n_blocks] = p.idx
                w[s, q, : p.n_blocks] = p.w
        sh = NamedSharding(self.mesh, P("shard", None, None))
        return fn(self.block_docs, self.block_tfs, self.doc_lens,
                  jnp.float32(self.avgdl),
                  jax.device_put(idx, sh), jax.device_put(w, sh))

    def search_batch(self, queries: Sequence[Sequence[str]], k: int,
                     prune: bool = True, count_hits: bool = False):
        """Q queries -> (scores [Q,k], original corpus doc ids [Q,k]) in two
        device dispatches (phase-1 theta + phase-2 exact over survivors).
        See ops/bm25.py Bm25Executor.top_k_batch for the soundness
        argument; here phase-1 theta comes from the GLOBAL top-k across
        shards, so pruning tightens with every shard's evidence.

        With ``count_hits`` a third return carries matched-doc counts
        [Q] from the score plane; ``last_hits_exact`` records whether
        every block was gathered (exact) or only survivors (lower
        bound)."""
        plans = [self._plans(t) for t in queries]
        fn = self._batch_fn(k, counted=count_hits)
        total = sum(p.n_blocks for per in plans for p in per)
        qb_max = max((p.n_blocks for per in plans for p in per), default=1)
        qb_pad = qb_bucket(max(qb_max, 1))
        if not prune or qb_max <= P1_BUCKET:
            # every plan fits phase 1 whole — pruning cannot pay
            self.last_prune_stats = (total, total)
            self.last_hits_exact = True
            return self._run_batch(fn, plans, qb_pad)
        p1 = [[p.top_by_ub(P1_BUCKET) for p in per] for per in plans]
        s1 = self._run_batch(self._batch_fn(k), p1, P1_BUCKET)[0]
        theta = np.asarray(s1)[:, k - 1]
        p2 = [[p.survivors(float(theta[q])) for p in per]
              for q, per in enumerate(plans)]
        scored = sum(p.n_blocks for per in p2 for p in per)
        p1_cost = sum(p.n_blocks for per in p1 for p in per)
        self.last_prune_stats = (total, min(scored + p1_cost, total))
        self.last_hits_exact = scored >= total
        qb2_max = max((p.n_blocks for per in p2 for p in per), default=1)
        qb2 = qb_bucket(max(qb2_max, 1))
        return self._run_batch(fn, p2, qb2)


# ---------------------------------------------------------------------------
# sharded sparse (rank_features / text_expansion)
# ---------------------------------------------------------------------------

def _local_sparse_scores(block_docs, block_weights, block_idx, qw,
                         n_per_shard: int):
    """Per-shard linear sparse scoring: gather feature blocks, contrib =
    query_weight * stored_weight, scatter-add (the text_expansion scoring
    of execute._h_text_expansion, distributed)."""
    docs = block_docs[block_idx]              # [QB, BLOCK]
    w = block_weights[block_idx]
    valid = docs >= 0
    safe = jnp.where(valid, docs, 0)
    contrib = qw[:, None] * w
    contrib = jnp.where(valid, contrib, 0.0)
    scores = jnp.zeros((n_per_shard,), jnp.float32)
    scores = scores.at[safe.reshape(-1)].add(contrib.reshape(-1),
                                             mode="drop")
    return jnp.where(scores > 0, scores, -jnp.inf)


def make_sharded_sparse(mesh: Mesh, n_per_shard: int, k: int):
    """Compile the distributed sparse-retrieval program:
    fn(block_docs [S,NB,B], block_weights [S,NB,B], block_idx [S,QB],
    qw [S,QB]) -> (scores [k], global ids [k])."""

    def local(block_docs, block_weights, block_idx, qw):
        s = _local_sparse_scores(block_docs[0], block_weights[0],
                                 block_idx[0], qw[0], n_per_shard)
        return _global_topk_1d(s, k, n_per_shard)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P("shard", None, None), P("shard", None, None),
                  P("shard", None), P("shard", None)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return profiled_callable("sharded_sparse", fn)


class ShardedFeaturesIndex:
    """rank_features corpus partitioned by doc over the mesh 'shard' axis
    (the text_expansion serving substrate — ShardedTextIndex's layout with
    stored weights instead of tfs and linear scoring)."""

    @classmethod
    def from_features_sources(cls, mesh: Mesh, sources,
                              qb_bucket_min: int = 8
                              ) -> "ShardedFeaturesIndex":
        """``sources``: ordered [(features_field_or_None, live, n_docs)]
        concatenated into a global doc space; tombstones dropped at build
        time."""
        obj = cls.__new__(cls)
        n_shards = mesh.shape["shard"]
        n = sum(n_docs for _, _, n_docs in sources)
        per = next_pow2(max(-(-n // max(n_shards, 1)), 1), minimum=BLOCK)
        shard_postings: List[Dict[str, Dict[int, float]]] = \
            [dict() for _ in range(n_shards)]
        base = 0
        for ff, live, n_docs in sources:
            if ff is None or n_docs == 0:
                base += n_docs
                continue
            live = np.asarray(live[:n_docs], bool)
            for feat, fid in ff.features.items():
                s0 = int(ff.feat_block_start[fid])
                cnt = int(ff.feat_block_count[fid])
                docs = ff.block_docs[s0 : s0 + cnt].reshape(-1)
                ws = ff.block_weights[s0 : s0 + cnt].reshape(-1)
                m = docs >= 0
                docs, ws = docs[m], ws[m]
                m = (docs < n_docs) & live[np.minimum(docs, n_docs - 1)]
                for d, wv in zip((base + docs[m]).tolist(),
                                 ws[m].tolist()):
                    sp = shard_postings[d % n_shards].setdefault(feat, {})
                    sp[d // n_shards] = float(wv)
            base += n_docs

        packed = []
        for s in range(n_shards):
            blocks_d, blocks_w = [], []
            index: Dict[str, Tuple[int, int]] = {}
            for t, posting in shard_postings[s].items():
                entries = sorted(posting.items())
                nb = max(1, -(-len(entries) // BLOCK))
                index[t] = (len(blocks_d), nb)
                d = np.full(nb * BLOCK, -1, np.int32)
                w = np.zeros(nb * BLOCK, np.float32)
                d[: len(entries)] = [e[0] for e in entries]
                w[: len(entries)] = [e[1] for e in entries]
                blocks_d.extend(d.reshape(nb, BLOCK))
                blocks_w.extend(w.reshape(nb, BLOCK))
            if not blocks_d:
                blocks_d = [np.full(BLOCK, -1, np.int32)]
                blocks_w = [np.zeros(BLOCK, np.float32)]
            packed.append((np.stack(blocks_d), np.stack(blocks_w), index))

        nb_max = next_pow2(max(p[0].shape[0] for p in packed))
        bd = np.full((n_shards, nb_max, BLOCK), -1, np.int32)
        bw = np.zeros((n_shards, nb_max, BLOCK), np.float32)
        obj.term_index = []
        for s, (d, w, index) in enumerate(packed):
            bd[s, : d.shape[0]] = d
            bw[s, : w.shape[0]] = w
            obj.term_index.append(index)
        obj.mesh = mesh
        obj.n_shards = n_shards
        obj.n_docs = n
        obj.n_per_shard = per
        obj.qb_bucket_min = qb_bucket_min
        obj.block_docs = jax.device_put(
            bd, NamedSharding(mesh, P("shard", None, None)))
        obj.block_weights = jax.device_put(
            bw, NamedSharding(mesh, P("shard", None, None)))
        obj._compiled = {}
        return obj

    def _prep(self, expansion) -> Tuple[np.ndarray, np.ndarray]:
        per_idx: List[List[int]] = [[] for _ in range(self.n_shards)]
        per_w: List[List[float]] = [[] for _ in range(self.n_shards)]
        for feat, weight in expansion:
            for s in range(self.n_shards):
                entry = self.term_index[s].get(feat)
                if entry is None:
                    continue
                start, count = entry
                for b_ in range(start, start + count):
                    per_idx[s].append(b_)
                    per_w[s].append(float(weight))
        qb = max(max((len(x) for x in per_idx), default=1), 1)
        qb_pad = next_pow2(qb, minimum=self.qb_bucket_min)
        idx = np.zeros((self.n_shards, qb_pad), np.int32)
        w = np.zeros((self.n_shards, qb_pad), np.float32)
        for s in range(self.n_shards):
            idx[s, : len(per_idx[s])] = per_idx[s]
            w[s, : len(per_w[s])] = per_w[s]
        return idx, w

    def search_batch(self, expansions, k: int):
        """[(feature, weight)] expansions -> (scores [Q, k], original ids
        [Q, k]); one compiled dispatch per query (expansions are tens of
        features — the gather is tiny)."""
        out_s, out_i = [], []
        for expansion in expansions:
            idx, w = self._prep(expansion)
            key = (k, idx.shape[1])
            fn = self._compiled.get(key)
            if fn is None:
                fn = make_sharded_sparse(self.mesh, self.n_per_shard, k)
                self._compiled[key] = fn
            sh = NamedSharding(self.mesh, P("shard", None))
            s, i = fn(self.block_docs, self.block_weights,
                      jax.device_put(idx, sh), jax.device_put(w, sh))
            out_s.append(np.asarray(s))
            out_i.append(to_original_ids(i, self.n_shards,
                                         self.n_per_shard))
        return np.stack(out_s), np.stack(out_i)


# ---------------------------------------------------------------------------
# fused hybrid (BM25 + kNN + RRF) — one program, no host round-trips
# ---------------------------------------------------------------------------

def make_sharded_hybrid(mesh: Mesh, n_per_shard: int, k: int,
                        rank_constant: int = 60,
                        similarity: str = "cosine",
                        k1: float = DEFAULT_K1, b: float = DEFAULT_B):
    """Distributed hybrid retrieval: BM25 and kNN branches execute locally,
    each produces a global top-k via all_gather, and RRF fuses on device —
    the BASELINE config-4 path as a single compiled program."""

    def local(block_docs, block_tfs, doc_lens, avgdl, block_idx, block_w,
              matrix, norms, valid, qvec):
        bscores = _local_bm25_scores(block_docs[0], block_tfs[0], doc_lens[0],
                                     avgdl, block_idx[0], block_w[0],
                                     n_per_shard, k1, b)
        vscores = _local_knn_scores(matrix[0], norms[0], valid[0],
                                    qvec[None, :], similarity)[0]

        _, bm25_ids = _global_topk_1d(bscores, k, n_per_shard)
        _, knn_ids = _global_topk_1d(vscores, k, n_per_shard)

        # --- RRF fuse on the (replicated) global id lists; -1 ids mark
        # below-threshold slots and must not earn rank credit
        ranks = jnp.arange(1, k + 1, dtype=jnp.float32)
        ids = jnp.concatenate([bm25_ids, knn_ids])
        contrib_r = jnp.concatenate([1.0 / (rank_constant + ranks)] * 2)
        present = ids >= 0
        contrib_r = jnp.where(present, contrib_r, 0.0)
        # dedupe: score(id) = sum of contributions where ids match
        eq = ids[:, None] == ids[None, :]
        fused = eq.astype(jnp.float32) @ contrib_r
        first = jnp.argmax(eq, axis=1) == jnp.arange(2 * k)  # keep first occurrence
        fused = jnp.where(first & present, fused, -jnp.inf)
        fs, fpos = jax.lax.top_k(fused, k)
        return fs, jnp.where(jnp.isfinite(fs), ids[fpos], -1)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P("shard", None, None), P("shard", None, None),
                  P("shard", None), P(), P("shard", None), P("shard", None),
                  P("shard", None, None), P("shard", None), P("shard", None),
                  P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return profiled_callable("sharded_hybrid", fn)
