"""Dense-vector similarity on device.

Replaces the reference's script_score vector loops —
ScoreScriptUtils.cosineSimilarity / dotProduct / l2norm iterating binary doc
values per document (x-pack/plugin/vectors/.../query/ScoreScriptUtils.java:132,151)
— with a tiled MXU matmul over the HBM-resident, segment-padded vector matrix,
fused with top-k. Scores use the same positive-score transforms ES applies:

  cosine:      (1 + cos) / 2
  dot_product: sigmoid-free 0.5 + dot/2 for normalized vectors is ES 8.x;
               this snapshot's painless returned raw dot — we use the
               standard modern transform for ranking stability
  l2_norm:     1 / (1 + dist)

bf16 is used for the multiply (MXU native) with f32 accumulation.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from elasticsearch_tpu.ops.device_segment import DeviceVectors
from elasticsearch_tpu.search.device_profile import profiled_jit
from elasticsearch_tpu.search.telemetry import record_dispatch


@profiled_jit("knn_vector_scores", static_argnames=("similarity",))
def vector_scores(matrix: jnp.ndarray,     # [N_pad, D] f32
                  norms: jnp.ndarray,      # [N_pad] f32
                  exists: jnp.ndarray,     # [N_pad] bool
                  query: jnp.ndarray,      # [D] f32
                  similarity: str = "cosine") -> jnp.ndarray:
    """Dense similarity scores [N_pad]; missing vectors score 0."""
    q = query.astype(jnp.bfloat16)
    m = matrix.astype(jnp.bfloat16)
    dots = jax.lax.dot_general(
        m, q[:, None],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0]                                                    # [N_pad] f32
    if similarity == "dot_product":
        scores = 0.5 + dots / 2.0
    elif similarity == "cosine":
        qn = jnp.linalg.norm(query) + 1e-30
        cos = dots / (norms * qn + 1e-30)
        scores = (1.0 + cos) / 2.0
    else:  # l2_norm
        q2 = jnp.sum(query * query)
        d2 = norms * norms + q2 - 2.0 * dots
        d2 = jnp.maximum(d2, 0.0)
        scores = 1.0 / (1.0 + jnp.sqrt(d2))
    return jnp.where(exists, scores, 0.0)


@profiled_jit("knn_topk", static_argnames=("similarity", "k"))
def knn_topk(matrix, norms, exists, live, query, k: int,
             similarity: str = "cosine") -> Tuple[jnp.ndarray, jnp.ndarray]:
    scores = vector_scores(matrix, norms, exists, query, similarity)
    scores = jnp.where(live & exists, scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def _batch_scores(matrix, norms, queries, similarity: str) -> jnp.ndarray:
    """[B, N_pad] similarity plane from one [B, D] x [D, N] MXU matmul
    (bf16 multiply, f32 accumulate) — shared by the masked and unmasked
    batch kernels so their per-row arithmetic cannot diverge. The
    positive-score transform is _coarse_similarity, the same one the
    quantized coarse pass applies to its rescaled int8 dots."""
    q = queries.astype(jnp.bfloat16)
    m = matrix.astype(jnp.bfloat16)
    dots = jax.lax.dot_general(
        q, m,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # [B, N_pad]
    return _coarse_similarity(dots, norms, queries, similarity)


def knn_topk_body(matrix, norms, allowed, queries, masks, k: int,
                  similarity: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """EXACT batched top-k over one vector plane: the ``_batch_scores``
    matmul + eligibility mask + top_k, shared VERBATIM by the
    single-shard batch kernels and the mesh slot kernel
    (parallel/mesh.py ``mesh_knn_topk``) — the ``bm25_flat_body``
    precedent, so mesh==fanout parity is structural. ``allowed`` [N] is
    the per-plane eligibility row (live & exists, plus a shared filter
    when every member carries the same one); ``masks`` [B, N] is the
    per-query filter stack for heterogeneous filters, or None."""
    scores = _batch_scores(matrix, norms, queries, similarity)
    ok = allowed[None, :] if masks is None else (allowed[None, :] & masks)
    ts, td = jax.lax.top_k(jnp.where(ok, scores, -jnp.inf), k)
    return ts, td


@profiled_jit("knn_topk_batch", static_argnames=("similarity", "k"))
def knn_topk_batch(matrix, norms, exists, live, queries, k: int,
                   similarity: str = "cosine") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched kNN: queries [B, D] -> (scores [B, k], docs [B, k]).

    One big [B, D] x [D, N] MXU matmul — the throughput shape for the
    SIFT1M-style benchmark."""
    return knn_topk_body(matrix, norms, live & exists, queries, None, k,
                         similarity)


@profiled_jit("knn_topk_batch_masked",
              static_argnames=("similarity", "k"))
def knn_topk_batch_masked(matrix, norms, exists, live, queries, masks,
                          k: int, similarity: str = "cosine"
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Filtered batched kNN: per-query filter masks [B, N_pad] ride the
    same [B, D] x [D, N] matmul — the filtered-kNN serving shape
    (autocomplete / faceted nav), where Q concurrent queries each carry
    their own filter-context mask but share the corpus scan."""
    return knn_topk_body(matrix, norms, live & exists, queries, masks, k,
                         similarity)


# Row floor of the padded query batch. XLA picks the [B, D] x [D, N]
# dot's accumulation order by shape, so a solo query padded to B=1 and
# the same query inside a wave padded to B=4 can differ in the last ulp;
# one floor puts every batch of up to 8 queries through the same shape
# ("solo is a batch of one" holds bit-for-bit).
MIN_QUERY_ROWS = 8


def pad_queries_pow2(queries) -> Tuple[np.ndarray, int]:
    """Pad the query batch to a pow2 row count of at least
    ``MIN_QUERY_ROWS`` (zeros) so the jit cache stays warm across batch
    sizes; returns (padded, n_real). One implementation shared by the
    exact executor and the quantized plane pass — their pads must stay
    in lockstep (``pad_mask_rows_pow2`` takes its row count from here)."""
    from elasticsearch_tpu.index.segment import next_pow2
    q_host = np.asarray(queries, np.float32)
    n_real = q_host.shape[0]
    n_pad = next_pow2(max(n_real, 1), minimum=MIN_QUERY_ROWS)
    if n_pad != n_real:
        q_host = np.concatenate(
            [q_host, np.zeros((n_pad - n_real, q_host.shape[1]),
                              np.float32)])
    return q_host, n_real


def pad_mask_rows_pow2(masks, n_pad: int) -> np.ndarray:
    """Stacked per-query filter masks padded to the query batch's pow2
    row count; padded rows stay False (they match nothing)."""
    m = np.asarray(masks)
    out = np.zeros((n_pad, m.shape[1]), bool)
    out[: m.shape[0]] = m
    return out


def _quantize_queries(queries: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-row int8 quantization of the query batch (the doc
    side is quantized once at plane pack time)."""
    qmax = jnp.max(jnp.abs(queries), axis=1, keepdims=True)
    qscale = jnp.maximum(qmax / 127.0, 1e-30)
    qq = jnp.clip(jnp.round(queries / qscale), -127, 127).astype(jnp.int8)
    return qq, qscale


def _coarse_similarity(dots, norms, queries, similarity: str) -> jnp.ndarray:
    if similarity == "dot_product":
        return 0.5 + dots / 2.0
    if similarity == "cosine":
        qn = jnp.linalg.norm(queries, axis=1, keepdims=True) + 1e-30
        return (1.0 + dots / (norms[None, :] * qn + 1e-30)) / 2.0
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)
    d2 = jnp.maximum(norms[None, :] ** 2 + q2 - 2.0 * dots, 0.0)
    return 1.0 / (1.0 + jnp.sqrt(d2))


def _coarse_plane(q8, scales, norms, queries, similarity: str
                  ) -> jnp.ndarray:
    """[B, N_pad] coarse similarity: int8 x int8 MXU matmul (int32
    accumulate, rescaled to f32) + the positive-score transform. Shared
    by the masked and unmasked coarse kernels."""
    qq, qscale = _quantize_queries(queries)
    dots = jax.lax.dot_general(
        qq, q8,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32) * (qscale * scales[None, :])     # [B, N_pad]
    return _coarse_similarity(dots, norms, queries, similarity)


@profiled_jit("knn_coarse", static_argnames=("similarity", "kprime"))
def knn_coarse_candidates(q8, scales, norms, allowed, queries,
                          kprime: int, similarity: str = "cosine"
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized coarse pass over the FULL plane: (coarse scores [B, k'],
    candidate doc ids [B, k']) per query. Ranking-only — the exact f32
    re-rank (knn_rerank_exact) restores golden scores for the survivors;
    the coarse scores feed the adaptive-depth margin check (the k'-th
    coarse score bounds what any EXCLUDED doc could have scored)."""
    s = _coarse_plane(q8, scales, norms, queries, similarity)
    s = jnp.where(allowed[None, :], s, -jnp.inf)
    return jax.lax.top_k(s, kprime)


@profiled_jit("knn_coarse_masked",
              static_argnames=("similarity", "kprime"))
def knn_coarse_candidates_masked(q8, scales, norms, allowed, queries,
                                 masks, kprime: int,
                                 similarity: str = "cosine"
                                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Coarse pass with per-query filter masks [B, N_pad] (filtered kNN)."""
    s = _coarse_plane(q8, scales, norms, queries, similarity)
    s = jnp.where(allowed[None, :] & masks, s, -jnp.inf)
    return jax.lax.top_k(s, kprime)


def _rerank_scores(matrix, norms, queries, cand, similarity: str
                   ) -> jnp.ndarray:
    """Exact f32 scores [B, K'] of the gathered candidate rows, with the
    SAME bf16-multiply/f32-accumulate arithmetic and positive-score
    transforms as _batch_scores — one implementation, so a scoring fix
    cannot diverge between the masked and unmasked re-rank kernels."""
    rows = matrix[cand]                                    # [B, K', D]
    dots = jnp.einsum("bd,bkd->bk", queries.astype(jnp.bfloat16),
                      rows.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    bnorms = norms[cand]                                   # [B, K']
    if similarity == "dot_product":
        return 0.5 + dots / 2.0
    if similarity == "cosine":
        qn = jnp.linalg.norm(queries, axis=1, keepdims=True) + 1e-30
        return (1.0 + dots / (bnorms * qn + 1e-30)) / 2.0
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)
    d2 = jnp.maximum(bnorms * bnorms + q2 - 2.0 * dots, 0.0)
    return 1.0 / (1.0 + jnp.sqrt(d2))


def knn_rerank_body(matrix, norms, allowed, queries, cand, coarse_s,
                    masks, k: int, similarity: str
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The ONE exact-re-rank body, shared by the single-shard profiled
    kernels below and the mesh per-slot variant (parallel/mesh.py
    ``mesh_knn_rerank``) so their scores cannot diverge.

    Candidates are sorted ascending by doc id first: ``lax.top_k`` breaks
    score ties by LOWER index, so sorting makes re-rank tie-breaks agree
    with the dense exact kernel's lower-doc-id-wins order — quantization
    must not reorder equal-scored docs. Returns (scores [B, k], doc ids
    [B, k], eps [B]) where ``eps`` is the max observed |exact - coarse|
    deviation among the re-ranked candidates — the empirical error
    estimate the adaptive-depth margin check scales from."""
    order = jnp.argsort(cand, axis=1)
    cand_s = jnp.take_along_axis(cand, order, axis=1)
    cs_s = jnp.take_along_axis(coarse_s, order, axis=1)
    s = _rerank_scores(matrix, norms, queries, cand_s, similarity)
    ok = allowed[cand_s]
    if masks is not None:
        ok = ok & jnp.take_along_axis(masks, cand_s, axis=1)
    sm = jnp.where(ok, s, -jnp.inf)
    ts, pos = jax.lax.top_k(sm, k)
    td = jnp.take_along_axis(cand_s, pos, axis=1)
    td = jnp.where(jnp.isfinite(ts), td, -1)
    both = ok & jnp.isfinite(cs_s)
    eps = jnp.max(jnp.where(both, jnp.abs(s - cs_s), 0.0), axis=1)
    return ts, td, eps


@profiled_jit("knn_rerank", static_argnames=("similarity", "k"))
def knn_rerank_exact(matrix, norms, allowed, queries, cand, coarse_s,
                     k: int, similarity: str = "cosine"
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Exact f32 re-rank of the coarse candidates: identical top-k to the
    exact path whenever the true top-k survives the coarse pass — which
    the adaptive-depth margin check (plane_exec) proves per query from
    the returned eps, deepening and re-dispatching when it cannot."""
    return knn_rerank_body(matrix, norms, allowed, queries, cand,
                           coarse_s, None, k, similarity)


@profiled_jit("knn_rerank_masked",
              static_argnames=("similarity", "k"))
def knn_rerank_exact_masked(matrix, norms, allowed, queries, cand,
                            coarse_s, masks, k: int,
                            similarity: str = "cosine"
                            ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                       jnp.ndarray]:
    """knn_rerank_exact with per-query filter masks re-applied to the
    gathered candidates (a masked-out doc must stay out even if the
    coarse pass leaked it in)."""
    return knn_rerank_body(matrix, norms, allowed, queries, cand,
                           coarse_s, masks, k, similarity)


class KnnExecutor:
    """Per-(segment, field) exact kNN executor."""

    def __init__(self, device_vectors: DeviceVectors):
        self.dev = device_vectors

    def top_k(self, query, live, k: int):
        record_dispatch()
        q = jnp.asarray(query, jnp.float32)
        return knn_topk(self.dev.matrix, self.dev.norms, self.dev.exists,
                        live, q, k, self.dev.similarity)

    def top_k_batch(self, queries, live, k: int, masks=None):
        """Batched exact kNN over Q query vectors: ONE [Q, D] x [D, N] MXU
        matmul instead of Q matvec dispatches (the serving-path counterpart
        of the bench-only knn_topk_batch shape). The query dimension pads
        to a pow2 bucket so the jit cache stays warm across batch sizes;
        padded rows come back sliced off.

        ``masks`` carries the filter-context of filtered kNN: a single
        [N_pad] bool mask shared by every query (the autocomplete /
        faceted-nav case — it simply folds into ``live``, exactly as the
        solo path's ``live & fmask``), or a [Q, N_pad] stack of per-query
        masks applied inside the one masked matmul dispatch."""
        record_dispatch()
        q_host, n_real = pad_queries_pow2(queries)
        if masks is not None and getattr(masks, "ndim", 1) == 2:
            m_host = pad_mask_rows_pow2(masks, q_host.shape[0])
            s, d = knn_topk_batch_masked(
                self.dev.matrix, self.dev.norms, self.dev.exists, live,
                jnp.asarray(q_host), jnp.asarray(m_host), k,
                self.dev.similarity)
            return s[:n_real], d[:n_real]
        if masks is not None:
            live = live & masks                   # shared filter mask
        s, d = knn_topk_batch(self.dev.matrix, self.dev.norms,
                              self.dev.exists, live,
                              jnp.asarray(q_host), k, self.dev.similarity)
        return s[:n_real], d[:n_real]

    def scores(self, query, live) -> jnp.ndarray:
        q = jnp.asarray(query, jnp.float32)
        s = vector_scores(self.dev.matrix, self.dev.norms, self.dev.exists,
                          q, self.dev.similarity)
        return jnp.where(live, s, 0.0)
