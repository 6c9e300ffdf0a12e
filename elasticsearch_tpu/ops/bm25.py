"""BM25 scoring on device.

Replaces the reference's hot loop — Lucene BulkScorer over postings with
LegacyBM25Similarity (search/query/QueryPhase.java:331,
index/similarity/SimilarityService.java:60) and TopScoreDocCollector top-k
(search/query/TopDocsCollectorContext.java:215) — with a block-at-a-time
device program:

1. host: resolve query terms -> posting-block indices + per-term idf
   (gather_query_blocks);
2. device: gather blocks, compute per-entry BM25 contributions on the VPU,
   scatter-add into a dense per-doc score vector, top-k.

Everything is static-shaped: block count and doc count are padded to pow2
buckets, so one compiled program serves many queries.

idf follows the reference's BM25: ln(1 + (N - df + 0.5) / (df + 0.5)).
"""

from __future__ import annotations

from collections import Counter
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from elasticsearch_tpu.index.segment import PostingsField, next_pow2
from elasticsearch_tpu.ops.device_segment import DevicePostings, gather_query_blocks
from elasticsearch_tpu.search.device_profile import profiled_jit
from elasticsearch_tpu.search.telemetry import record_dispatch, to_host

DEFAULT_K1 = 1.2
DEFAULT_B = 0.75


def idf(doc_count: int, doc_freq: int) -> float:
    """Reference BM25 idf (Lucene BM25Similarity.idfExplain)."""
    return float(np.log(1.0 + (doc_count - doc_freq + 0.5) / (doc_freq + 0.5)))


@profiled_jit("bm25_block_scores",
              static_argnames=("n_docs_pad", "k1", "b"))
def bm25_block_scores(block_docs: jnp.ndarray,     # [NB, BLOCK] int32, -1 pad
                      block_tfs: jnp.ndarray,      # [NB, BLOCK] f32
                      block_idx: jnp.ndarray,      # [QB] int32 gather indices
                      block_weight: jnp.ndarray,   # [QB] f32 (idf * query boost)
                      doc_lens: jnp.ndarray,       # [n_docs_pad] f32
                      avgdl: jnp.ndarray,          # scalar f32
                      n_docs_pad: int,
                      k1: float = DEFAULT_K1,
                      b: float = DEFAULT_B) -> jnp.ndarray:
    """Dense BM25 scores [n_docs_pad] for one query over one segment."""
    docs = block_docs[block_idx]            # [QB, BLOCK]
    tfs = block_tfs[block_idx]              # [QB, BLOCK]
    valid = docs >= 0
    safe_docs = jnp.where(valid, docs, 0)
    dl = doc_lens[safe_docs]                # [QB, BLOCK]
    norm = k1 * (1.0 - b + b * dl / avgdl)
    contrib = block_weight[:, None] * tfs * (k1 + 1.0) / (tfs + norm)
    contrib = jnp.where(valid, contrib, 0.0)
    scores = jnp.zeros((n_docs_pad,), jnp.float32)
    scores = scores.at[safe_docs.reshape(-1)].add(
        contrib.reshape(-1), mode="drop")
    return scores


@profiled_jit("bm25_topk",
              static_argnames=("n_docs_pad", "k1", "b", "k"))
def bm25_topk(block_docs, block_tfs, block_idx, block_weight, doc_lens, avgdl,
              live, n_docs_pad: int, k: int,
              k1: float = DEFAULT_K1, b: float = DEFAULT_B
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused BM25 scoring + live-mask + top-k. Returns (scores[k], docs[k]);
    empty slots have score -inf."""
    scores = bm25_block_scores(block_docs, block_tfs, block_idx, block_weight,
                               doc_lens, avgdl, n_docs_pad, k1=k1, b=b)
    scores = jnp.where(live & (scores > 0.0), scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


# number of highest-upper-bound blocks scored in phase 1 of the pruned
# path to establish the top-k score floor (theta). Swept on the zipfian
# bench corpus (r4): 64 beats 32 (tighter theta prunes more than the
# extra phase-1 gathers cost) and 128 overshoots.
P1_BUCKET = 64

# per-dispatch ceiling on the FLAT block count: each device temp is
# FB*BLOCK*4 bytes ([FB, 128] f32 gathers), and the program holds ~4 of
# them live — 4M cells = ~2GB/temp, safely inside a 16G HBM chip.
# Larger batches split into query chunks (one compile per chunk shape).
MAX_BATCH_CELLS = 4_000_000


def bm25_flat_body(block_docs, block_tfs,
                   flat_idx,    # [FB] int32 block gather ids (0 pad)
                   flat_w,      # [FB] f32 idf*boost (0 pad)
                   flat_q,      # [FB] int32 query id (0 pad)
                   doc_lens, flat_avgdl, live,
                   n_docs_pad: int, n_q: int,
                   k1: float = DEFAULT_K1, b: float = DEFAULT_B):
    """The ONE traced flat-BM25 body: gather the batch's blocks, compute
    per-entry contributions, scatter-add into a [n_q, n_docs_pad] score
    plane, mask to live matches. Returns (scores, matched) with dead
    slots already at -inf.

    Shared verbatim by ``_bm25_flat_kernel`` (single plane / segment),
    ``_bm25_flat_kernel_seg`` (per-segment counted channel) and the mesh
    kernel's per-slot body (parallel/mesh.py ``mesh_bm25_flat``) — same
    gather order, same f32 scatter-adds — so their scores are
    bit-compatible BY CONSTRUCTION, not by a golden suite catching drift
    after the fact.

    ``flat_avgdl`` [FB] carries each gathered block's avgdl: one scalar
    broadcast for a single-segment dispatch, the owning segment's value
    per block when the gather spans a multi-segment plane — so plane
    scores use exactly the per-segment length norm the per-segment path
    does."""
    docs = block_docs[flat_idx]             # [FB, BLOCK]
    tfs = block_tfs[flat_idx]               # [FB, BLOCK]
    valid = docs >= 0
    safe = jnp.where(valid, docs, 0)
    dl = doc_lens[safe]
    norm = k1 * (1.0 - b + b * dl / flat_avgdl[:, None])
    contrib = flat_w[:, None] * tfs * (k1 + 1.0) / (tfs + norm)
    contrib = jnp.where(valid, contrib, 0.0)
    # scatter into a [n_q, n_docs_pad] score plane via flattened targets
    tgt = flat_q[:, None] * n_docs_pad + safe
    scores = jnp.zeros((n_q * n_docs_pad,), jnp.float32)
    scores = scores.at[tgt.reshape(-1)].add(contrib.reshape(-1),
                                            mode="drop")
    scores = scores.reshape(n_q, n_docs_pad)
    matched = live[None, :] & (scores > 0.0)
    scores = jnp.where(matched, scores, -jnp.inf)
    return scores, matched


# The dense head mirror (ops/device_segment.py ``HeadMirror``): a query
# row reads at most DENSE_SLOTS of its terms as whole bf16 tf columns. The
# count is fixed, so the coarse program's shapes gain no dimension; a row
# with more head terms scatters the rest.
DENSE_SLOTS = 8


def tail_sizes(fb: int) -> list:
    """Flat-plan prefixes the coarse kernel can scatter from a plan of
    ``fb`` blocks (a ``qb_bucket``): 32 blocks, then 256 and x4 steps
    above. The flat plan keeps the bucket of every block its rows read,
    so the coarse programs are the ones a warm-up over posting work
    meets; a dynamic branch then scatters the smallest prefix that holds
    the tail, at most 4x its blocks above 256. Each prefix is a scatter
    the program compiles: x4 keeps a 65,536-block plan at six."""
    out, size = [], 32
    while size < fb:
        out.append(size)
        size *= 8 if size < 256 else 4
    return out + [fb]


def coarse_contrib(w, tfs, dl, avgdl, k1: float, b: float):
    """The coarse tier's bf16 BM25 contribution of tf ``tfs`` (bf16) for
    docs of length ``dl`` (bf16) under ``avgdl`` and weight ``w`` (f32),
    as f32. The one expression of the scatter and the dense passes, so
    a posting contributes the same value whichever pass reads it."""
    h = jnp.bfloat16
    norm = h(k1) * (h(1.0 - b) + h(b) * dl / avgdl.astype(h))
    return (w.astype(h) * tfs * h(k1 + 1.0) / (tfs + norm)) \
        .astype(jnp.float32)


def _coarse_scatter(scores, block_docs, block_tfs_q, flat_idx, flat_w,
                    flat_q, doc_lens_q, flat_avgdl, n_docs_pad: int,
                    k1: float, b: float):
    """Gather the flat plan's blocks and scatter-add their contributions
    into the flattened [n_q * n_docs_pad] f32 plane ``scores``."""
    docs = block_docs[flat_idx]             # [FB, BLOCK]
    tfs = block_tfs_q[flat_idx]             # [FB, BLOCK] bf16
    valid = docs >= 0
    safe = jnp.where(valid, docs, 0)
    contrib = coarse_contrib(flat_w[:, None], tfs, doc_lens_q[safe],
                             flat_avgdl[:, None], k1, b)
    contrib = jnp.where(valid, contrib, 0.0)
    tgt = flat_q[:, None] * n_docs_pad + safe
    return scores.at[tgt.reshape(-1)].add(contrib.reshape(-1), mode="drop")


def _dense_heads(dense, doc_lens_q, seg_ids, n_q: int, n_segs: int,
                 k1: float, b: float):
    """[n_q, n_docs_pad] f32 sum of each row's dense-slot contributions:
    ``dense`` = (heads [H + 1, n_docs_pad] bf16 tf columns, row H all
    zeros; slot_rows [n_q, DENSE_SLOTS] int32, H where unused; slot_w
    [n_q, DENSE_SLOTS] f32 idf*boost; seg_avgdl [n_segs] f32, each
    segment's effective avgdl). A doc norms against its own segment's
    avgdl, which the scatter reads per block. Rows fill their slots
    from the first, so only the slots some row uses are read."""
    heads, slot_rows, slot_w, seg_avgdl = dense
    avg = jnp.zeros(seg_ids.shape, jnp.float32)
    for s in range(n_segs):
        avg = jnp.where(seg_ids == s, seg_avgdl[s], avg)

    def first(m):
        def run():
            acc = jnp.zeros((n_q, seg_ids.shape[0]), jnp.float32)
            for j in range(m):
                tfs = heads[slot_rows[:, j]]    # [n_q, n_docs_pad] bf16
                c = coarse_contrib(slot_w[:, j, None], tfs,
                                   doc_lens_q[None, :], avg[None, :], k1, b)
                acc = acc + jnp.where(tfs > 0, c, 0.0)
            return acc
        return run
    used = jnp.max(jnp.sum(slot_rows < heads.shape[0] - 1, axis=1))
    return lax.switch(used, [first(m)
                             for m in range(slot_rows.shape[1] + 1)])


def bm25_coarse_body(block_docs, block_tfs_q, flat_idx, flat_w, flat_q,
                     doc_lens_q, flat_avgdl, live, seg_ids,
                     n_docs_pad: int, n_q: int, n_segs: int, kprime: int,
                     k1: float = DEFAULT_K1, b: float = DEFAULT_B,
                     tail=None, dense=None):
    """The quantized COARSE tier of the two-tier text path: the same
    gather/scatter shape as ``bm25_flat_body`` but over the plane's bf16
    mirrors (``block_tfs_q`` / ``doc_lens_q`` — half the HBM gather
    traffic, the scatter-bound classes' dominant cost), contributions
    computed in bf16 (``coarse_contrib``) and accumulated in f32. It is
    the only pass of the two tiers that reads every posting of every
    query term.

    ``dense`` (see ``_dense_heads``) serves a row's heaviest terms from
    whole tf columns, read contiguously, instead of scattering their
    postings; the flat plan then holds only the other terms' blocks (the
    tail), and only its prefix ``tail_sizes(FB)[tail]`` scatters
    (``tail`` an int32 scalar). Each contribution is the scatter's value
    bit for bit; only the order of the f32 adds differs. Without
    ``tail`` the whole flat plan scatters (the mesh program's per-slot
    body).

    Returns (coarse scores [n_q, kprime], candidate plane docs
    [n_q, kprime], per-segment match counts [n_q, n_segs]). The counts
    are EXACT despite the reduced precision: contributions are strictly
    positive wherever the f32 kernel's are (bf16 rounds positive
    products to positive values), so ``score > 0`` flags the same doc
    set — totals never depend on the re-rank. Candidate RANKING is
    coarse; the exact re-rank (``bm25_rerank_body``) looks each
    candidate up in the query's term ranges and restores golden scores,
    and the k'-th coarse score bounds what any excluded doc could have
    scored (the adaptive-depth margin input)."""
    if dense is None:
        scores = jnp.zeros((n_q * n_docs_pad,), jnp.float32)
    else:
        scores = _dense_heads(dense, doc_lens_q, seg_ids, n_q, n_segs,
                              k1, b).reshape(-1)

    def prefix(size):
        return lambda acc: _coarse_scatter(
            acc, block_docs, block_tfs_q, flat_idx[:size], flat_w[:size],
            flat_q[:size], doc_lens_q, flat_avgdl[:size], n_docs_pad, k1,
            b)
    if tail is None:
        scores = prefix(flat_idx.shape[0])(scores)
    else:
        # a conditional, not a loop: a loop would carry (and copy) the
        # whole block store on every call
        scores = lax.switch(tail, [prefix(size) for size in
                                   tail_sizes(flat_idx.shape[0])], scores)
    scores = scores.reshape(n_q, n_docs_pad)
    matched = live[None, :] & (scores > 0.0)
    scores = jnp.where(matched, scores, -jnp.inf)
    cs, cand = jax.lax.top_k(scores, kprime)
    onehot = jax.nn.one_hot(seg_ids, n_segs, dtype=jnp.int32)
    hits = matched.astype(jnp.int32) @ onehot       # [n_q, n_segs]
    return cs, cand, hits


def posting_tfs(block_docs, block_tfs, r_start, r_count, cand):
    """tf [n_q, R, C] of each candidate ``cand`` [n_q, C] in each term
    range of its query (``r_start`` / ``r_count`` [n_q, R]: a run of
    plane blocks), 0 where the range holds no posting of it.

    A term's blocks are contiguous and doc-sorted, every block's entry 0
    is a posting and only the last block pads its tail with -1 — so the
    blocks' first docs are the term's skip list, and the postings of a
    block are sorted up to the pad. Two branch-free bisections find the
    candidate: over the range's blocks for the last whose first doc is
    <= it, then over that block's lanes for the last posting <= it. Their
    step counts come from the plane's shape (log2 NB, log2 BLOCK), so
    the program does not depend on how many postings a query reads;
    every step gathers one int32 per (query, range, candidate)."""
    nb, width = block_docs.shape
    start = r_start[:, :, None]
    count = r_count[:, :, None]
    c = cand[:, None, :]
    blk = jnp.zeros(jnp.broadcast_shapes(start.shape, c.shape), jnp.int32)
    for s in reversed(range(max(nb - 1, 1).bit_length())):
        nxt = blk + (1 << s)
        first = block_docs[jnp.minimum(start + nxt, nb - 1), 0]
        blk = jnp.where((nxt < count) & (first <= c), nxt, blk)
    row = jnp.minimum(start + blk, nb - 1)
    lane = jnp.zeros_like(blk)
    for s in reversed(range(max(width - 1, 1).bit_length())):
        nxt = lane + (1 << s)
        doc = block_docs[row, jnp.minimum(nxt, width - 1)]
        lane = jnp.where((nxt < width) & (doc >= 0) & (doc <= c), nxt,
                         lane)
    hit = (block_docs[row, lane] == c) & (count > 0)
    return jnp.where(hit, block_tfs[row, lane], 0.0)


def bm25_rerank_body(block_docs, block_tfs, r_start, r_count, r_w,
                     r_avgdl, doc_lens, live, cand, coarse_s, k: int,
                     k1: float = DEFAULT_K1, b: float = DEFAULT_B):
    """The EXACT tier: re-score only the coarse candidates, each looked
    up in its query's term ranges (``posting_tfs``) instead of re-reading
    every posting — work n_q·R·k' lookups, whatever the posting count.

    ``r_start`` / ``r_count`` / ``r_w`` / ``r_avgdl`` [n_q, R] are the
    query's (segment, term) ranges in the flat plan's order (segment-
    major, then query-term order) with the plan's idf·boost and the
    segment's effective avgdl; padded ranges have count 0. Scores are
    bit-equal to ``bm25_flat_body``'s dense plane: each contribution is
    its f32 expression with the same operands, and a candidate's sum
    runs over the ranges in plan order from 0.0. A doc has postings only
    in its own segment's ranges and ``x + 0.0 == x``, so that is the
    order a sequential scatter-add over the flat plan adds it in.

    Candidates are sorted ascending by doc id first, making score-tie
    breaks agree with the dense kernel's lower-index-wins order. Returns
    (scores [n_q, k], plane docs [n_q, k], eps [n_q]) with ``eps`` the
    max observed |exact - coarse| among matched candidates — the
    adaptive margin's empirical error estimate."""
    order = jnp.argsort(cand, axis=1)
    cand_s = jnp.take_along_axis(cand, order, axis=1)
    cs_s = jnp.take_along_axis(coarse_s, order, axis=1)
    tfs = posting_tfs(block_docs, block_tfs, r_start, r_count, cand_s)
    dl = doc_lens[cand_s][:, None, :]
    norm = k1 * (1.0 - b + b * dl / r_avgdl[:, :, None])
    contrib = r_w[:, :, None] * tfs * (k1 + 1.0) / (tfs + norm)
    contrib = jnp.where(tfs > 0.0, contrib, 0.0)
    # an unrolled chain of adds: XLA keeps a float add chain's order,
    # where a reduction over the range axis may re-associate it
    cscores = jnp.zeros(cand_s.shape, jnp.float32)
    for r in range(r_start.shape[1]):
        cscores = cscores + contrib[:, r]
    ok = live[cand_s] & (cscores > 0.0)
    masked = jnp.where(ok, cscores, -jnp.inf)
    s, pos = jax.lax.top_k(masked, k)
    d = jnp.take_along_axis(cand_s, pos, axis=1)
    both = ok & jnp.isfinite(cs_s)
    eps = jnp.max(jnp.where(both, jnp.abs(cscores - cs_s), 0.0), axis=1)
    return s, d, eps


@profiled_jit("bm25_coarse",
              static_argnames=("n_docs_pad", "n_q", "n_segs", "kprime",
                               "k1", "b"))
def _bm25_coarse_kernel(block_docs, block_tfs_q, flat_idx, flat_w, flat_q,
                        doc_lens_q, flat_avgdl, live, seg_ids,
                        n_docs_pad: int, n_q: int, n_segs: int,
                        kprime: int, k1: float = DEFAULT_K1,
                        b: float = DEFAULT_B, tail=None, dense=None):
    return bm25_coarse_body(block_docs, block_tfs_q, flat_idx, flat_w,
                            flat_q, doc_lens_q, flat_avgdl, live, seg_ids,
                            n_docs_pad, n_q, n_segs, kprime, k1=k1, b=b,
                            tail=tail, dense=dense)


@profiled_jit("bm25_rerank", static_argnames=("k", "k1", "b"))
def _bm25_rerank_kernel(block_docs, block_tfs, r_start, r_count, r_w,
                        r_avgdl, doc_lens, live, cand, coarse_s, k: int,
                        k1: float = DEFAULT_K1, b: float = DEFAULT_B):
    return bm25_rerank_body(block_docs, block_tfs, r_start, r_count, r_w,
                            r_avgdl, doc_lens, live, cand, coarse_s, k,
                            k1=k1, b=b)


def range_bucket(rows) -> int:
    """R of the exact re-rank: a pow2 bucket (minimum 8) of the most
    term ranges a row plan holds."""
    return next_pow2(max([len(p.ranges[0]) for p in rows] + [1]),
                     minimum=8)


def rerank_ranges(rows, n_q_pad: int, block_avgdl: np.ndarray,
                  r_pad: Optional[int] = None):
    """The exact re-rank's host arguments: (r_start, r_count, r_w,
    r_avgdl) [n_q_pad, R] from each row plan's term ranges, with
    ``r_avgdl`` read off ``block_avgdl`` (the per-block avgdl the flat
    dispatch gathers, DFS override included) at each range's start. R
    is ``r_pad`` or ``range_bucket(rows)``; padding ranges have count
    0."""
    if r_pad is None:
        r_pad = range_bucket(rows)
    start = np.zeros((n_q_pad, r_pad), np.int32)
    count = np.zeros((n_q_pad, r_pad), np.int32)
    w = np.zeros((n_q_pad, r_pad), np.float32)
    avg = np.ones((n_q_pad, r_pad), np.float32)
    for qi, p in enumerate(rows):
        s, c, pw = p.ranges
        n = len(s)
        start[qi, :n], count[qi, :n], w[qi, :n] = s, c, pw
        avg[qi, :n] = block_avgdl[s]
    return start, count, w, avg


@profiled_jit("bm25_flat",
              static_argnames=("n_docs_pad", "n_q", "k", "k1", "b",
                               "counted"))
def _bm25_flat_kernel(block_docs, block_tfs, flat_idx, flat_w, flat_q,
                      doc_lens, flat_avgdl, live,
                      n_docs_pad: int, n_q: int, k: int,
                      k1: float = DEFAULT_K1, b: float = DEFAULT_B,
                      counted: bool = False):
    """Flat batched BM25 + top-k: the whole batch's blocks in ONE gather +
    scatter-add (``bm25_flat_body``), each block tagged with its query id.

    This replaces the padded [Q, QB] layout whose per-query gather lists
    all padded to the LARGEST plan in the batch — on zipfian query mixes
    that wasted >10x the gather/scatter work (r3 bench: 1,048,576 padded
    cells for 79,743 real survivor blocks). Here device work is
    proportional to the batch's ACTUAL block count, padded only up to one
    pow-ladder bucket.

    With ``counted`` the kernel also returns hits[n_q] = #docs with
    score > 0, read off the score plane it already computed. The count is
    EXACT for the blocks gathered: unpruned dispatches count all hits;
    pruned dispatches yield a LOWER bound (dropped blocks aren't
    observed) — the counts-then-skip collector
    (TopDocsCollectorContext.java:215) uses it to prove
    'total >= track_total_hits' without a dense pass."""
    scores, matched = bm25_flat_body(block_docs, block_tfs, flat_idx,
                                     flat_w, flat_q, doc_lens, flat_avgdl,
                                     live, n_docs_pad, n_q, k1=k1, b=b)
    s, d = jax.lax.top_k(scores, k)
    if counted:
        return s, d, jnp.sum(matched, axis=1, dtype=jnp.int32)
    return s, d


def bm25_topk_flat_counted(*args, **kw):
    return _bm25_flat_kernel(*args, **kw, counted=True)


@profiled_jit("bm25_flat_seg",
              static_argnames=("n_docs_pad", "n_q", "k", "k1", "b",
                               "n_segs"))
def _bm25_flat_kernel_seg(block_docs, block_tfs, flat_idx, flat_w, flat_q,
                          doc_lens, flat_avgdl, live, seg_ids,
                          n_docs_pad: int, n_q: int, k: int,
                          k1: float = DEFAULT_K1, b: float = DEFAULT_B,
                          n_segs: int = 1):
    """_bm25_flat_kernel with PER-SEGMENT match counts.

    ``seg_ids`` [n_docs_pad] maps each plane doc to its owning segment's
    position; hits come back [n_q, n_segs]. This is the plane analog of
    the totals-disabled per-segment contract: each segment reports
    "candidates found" truncated to the collection window (sum of
    min(matches, want) per segment), a number the fused whole-plane count
    cannot reproduce — so the kernel counts where the segments are."""
    scores, matched = bm25_flat_body(block_docs, block_tfs, flat_idx,
                                     flat_w, flat_q, doc_lens, flat_avgdl,
                                     live, n_docs_pad, n_q, k1=k1, b=b)
    s, d = jax.lax.top_k(scores, k)
    onehot = jax.nn.one_hot(seg_ids, n_segs, dtype=jnp.int32)
    hits = matched.astype(jnp.int32) @ onehot       # [n_q, n_segs]
    return s, d, hits


def flatten_plans(plans, fb_pad: int):
    """Concatenate per-query plans into flat (idx, w, qid) arrays of
    length fb_pad (block 0 / weight 0 / query 0 as padding)."""
    idx = np.zeros(fb_pad, np.int32)
    w = np.zeros(fb_pad, np.float32)
    qid = np.zeros(fb_pad, np.int32)
    off = 0
    for i, p in enumerate(plans):
        n = p.n_blocks
        idx[off : off + n] = p.idx
        w[off : off + n] = p.w
        qid[off : off + n] = i
        off += n
    return idx, w, qid


def qb_bucket(n: int, minimum: int = 32) -> int:
    """Gather-list bucket size: a coarse x8 ladder, x2 above 16K.

    Every distinct gather shape costs a full XLA compile (~seconds); pow2
    buckets churn with each query batch. The x8 ladder wastes at most 8x
    gather padding (device cost: <1ms) to cap the shape space at ~4
    compiles; above 16K blocks the padding waste dominates compile
    amortization, so the ladder tightens to x2. (BENCH_r06's bm25_flat
    bucket blow-up — 20 live shapes, 2 warmup recompile storms — was
    investigated as a ladder problem, but widening the x2 region to x4
    measurably HALVED CPU-fallback batch throughput while merging
    almost nothing: the hot sizes sit on shared rung boundaries, and
    the cardinality is really the (FB, n_q, k) cross-product of the
    bench's many traffic patterns. The ladder stays; the per-request
    program-variant churn — the ``counted`` flag flipping with batch
    composition — was removed in ``dispatch_flat`` instead, which is
    what keeps one serving pattern in single-digit buckets.)"""
    b = max(minimum, 1)
    while b < n:
        b *= 8 if b < 16384 else 2
    return b


def dense_slots(rows, mirror, n_q_pad: int):
    """Split each row plan between the dense head mirror and the scatter.

    A row's term (its ranges with one weight) goes dense when it is a
    head term of ``mirror`` (ops/device_segment.py ``HeadMirror``) whose
    every segment range carries that weight the same number of times m:
    it then takes m slots, heaviest terms first, while DENSE_SLOTS last;
    the rest stay in the scatter. Returns (tail row plans, slot_rows
    [n_q_pad, DENSE_SLOTS] int32 with ``mirror.n_heads`` where unused,
    slot_w [n_q_pad, DENSE_SLOTS] f32, slots used)."""
    slot_rows = np.full((n_q_pad, DENSE_SLOTS), mirror.n_heads, np.int32)
    slot_w = np.zeros((n_q_pad, DENSE_SLOTS), np.float32)
    tails, used = [], 0
    for qi, p in enumerate(rows):
        starts, counts, weights = p.ranges
        groups: dict = {}
        for j, (st, wt) in enumerate(zip(starts.tolist(),
                                         weights.tolist())):
            h = mirror.row_of_start.get(st)
            if h is not None:
                groups.setdefault((h, wt), []).append(j)
        keep = np.ones(len(starts), bool)
        n = 0
        for (h, wt), js in sorted(groups.items()):
            per = Counter(int(starts[j]) for j in js)
            m = len(js) // len(per)
            if set(per) != mirror.starts_of_row[h] or \
                    any(c != m for c in per.values()) or \
                    n + m > DENSE_SLOTS:
                continue
            slot_rows[qi, n: n + m] = h
            slot_w[qi, n: n + m] = wt
            keep[js] = False
            n += m
        used += n
        if n:
            mask = np.repeat(keep, counts)
            p = QueryPlan(p.idx[mask], p.w[mask], p.ub[mask],
                          p.other_ub[mask])
        tails.append(p)
    return tails, slot_rows, slot_w, used


_NO_RANGES = (np.zeros(0, np.int32), np.zeros(0, np.int32),
              np.zeros(0, np.float32))


class QueryPlan:
    """Host-side per-query block plan with block-max upper bounds.

    For each candidate posting block: its gather index, weight (idf*boost),
    and ub = weight*(k1+1)*block_max_impact — the max BM25 contribution any
    doc in the block can receive from its term. other_ub is the sum of the
    OTHER query terms' global per-doc bounds, so ub + other_ub bounds the
    total score of every doc in the block (the WAND invariant).

    ``ranges`` (start [T] int32, count [T] int32, weight [T] f32) names
    the plan's per-term block runs in plan order, which the exact
    re-rank looks candidates up in. A plan without blocks has none;
    ``survivors`` and ``top_by_ub`` keep only some blocks of a run, so
    their plans carry None."""

    __slots__ = ("idx", "w", "ub", "other_ub", "ranges")

    def __init__(self, idx, w, ub, other_ub, ranges=None):
        self.idx = np.asarray(idx, np.int32)
        self.w = np.asarray(w, np.float32)
        self.ub = np.asarray(ub, np.float64)
        self.other_ub = np.asarray(other_ub, np.float64)
        if ranges is None and not len(self.idx):
            ranges = _NO_RANGES
        self.ranges = ranges

    @property
    def n_blocks(self) -> int:
        return len(self.idx)

    def survivors(self, theta: float) -> "QueryPlan":
        """Blocks whose docs could still reach the top-k given score floor
        theta. Sound: a doc in a dropped block scores at most ub + other_ub
        < theta, so it provably cannot enter the final top-k. The small
        slack absorbs f32-vs-f64 rounding between device scores and host
        bounds."""
        if not np.isfinite(theta):
            return self
        # slack scales with |theta| so accumulated f32 scatter-add error on
        # large scores can't unsoundly drop a block holding a true top-k doc
        slack = max(1e-4, 1e-5 * abs(theta))
        keep = (self.ub + self.other_ub) >= (theta - slack)
        return QueryPlan(self.idx[keep], self.w[keep], self.ub[keep],
                         self.other_ub[keep])

    def top_by_ub(self, m: int) -> "QueryPlan":
        if self.n_blocks <= m:
            return self
        order = np.argsort(-self.ub, kind="stable")[:m]
        return QueryPlan(self.idx[order], self.w[order], self.ub[order],
                         self.other_ub[order])

    @staticmethod
    def concat(plans: "list[QueryPlan]",
               idx_offsets=None) -> "QueryPlan":
        """One plan from many (the plane path: per-segment plans joined
        with each segment's block base added to its gather indices and
        range starts). Per-block bounds are segment-local and stay valid
        unchanged."""
        if not plans:
            return QueryPlan([], [], [], [])
        offs = [0 if idx_offsets is None else int(idx_offsets[i])
                for i in range(len(plans))]
        ranges = None
        if all(p.ranges is not None for p in plans):
            ranges = (np.concatenate([p.ranges[0] + np.int32(off)
                                      for p, off in zip(plans, offs)]),
                      np.concatenate([p.ranges[1] for p in plans]),
                      np.concatenate([p.ranges[2] for p in plans]))
        return QueryPlan(np.concatenate([p.idx + np.int32(off)
                                         for p, off in zip(plans, offs)]),
                         np.concatenate([p.w for p in plans]),
                         np.concatenate([p.ub for p in plans]),
                         np.concatenate([p.other_ub for p in plans]),
                         ranges)


# doc-space granularity of the range-partitioned WAND bound: other-term
# maxima are tracked per GRID-doc cell, so a stopword block only inherits a
# rare term's bound if the rare term actually has postings in the block's
# doc range (BMW's aligned block maxima, re-expressed on a fixed grid for
# vectorized host planning). Swept on the zipfian bench corpus (r4):
# 64-doc cells prune ~6 points more of the block space than 256 at equal
# host planning cost; 32 pays more planning than it saves.
WAND_GRID = 64


class _RangeMax:
    """Sparse-table max over a per-term coarse doc-range array: build
    O(R log R), vectorized O(1) range-max queries."""

    def __init__(self, cell_ub: np.ndarray):
        self.levels = [cell_ub]
        r = len(cell_ub)
        span = 1
        while span * 2 <= r:
            prev = self.levels[-1]
            self.levels.append(np.maximum(prev[: r - span * 2 + 1],
                                          prev[span : r - span + 1]))
            span *= 2

    def query(self, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
        """Elementwise max over cells [lo_i, hi_i] (inclusive, lo <= hi)."""
        length = hi - lo + 1
        j = np.maximum(np.int64(np.log2(np.maximum(length, 1))), 0)
        out = np.zeros(len(lo), np.float64)
        for jj in np.unique(j):
            lvl = self.levels[min(int(jj), len(self.levels) - 1)]
            m = j == jj
            span = 1 << min(int(jj), len(self.levels) - 1)
            a = lvl[np.minimum(lo[m], len(lvl) - 1)]
            b_ = lvl[np.minimum(np.maximum(hi[m] - span + 1, 0),
                                len(lvl) - 1)]
            out[m] = np.maximum(a, b_)
        return out


class TermCellIndex:
    """Per-term posting-level WAND bound index, built lazily once per term.

    For a term, records which WAND_GRID-doc cells hold any of its postings
    and the max impact within each (exact, from true tfs and doc lengths),
    compressed to touched cells with a sparse table for O(1) range-max.
    Query-independent: multiply by idf*boost at query time."""

    PAIR_CACHE_CAP = 8192

    def __init__(self, block_docs: np.ndarray, block_tfs: np.ndarray,
                 doc_lens: np.ndarray, avgdl: float,
                 k1: float = DEFAULT_K1, b: float = DEFAULT_B):
        self.block_docs = block_docs
        self.block_tfs = block_tfs
        self.doc_lens = doc_lens
        self.avgdl = max(avgdl, 1e-9)
        self.k1 = k1
        self.b = b
        self._cache: dict = {}
        self._range_cache: dict = {}
        self._pair_cache: dict = {}

    def term_cells(self, start: int, count: int):
        """(touched cells ascending [int64], RangeMax over their impacts)."""
        got = self._cache.get(start)
        if got is not None:
            return got
        docs = self.block_docs[start : start + count].reshape(-1)
        tfs = self.block_tfs[start : start + count].reshape(-1)
        valid = docs >= 0
        d = docs[valid].astype(np.int64)
        f = tfs[valid].astype(np.float64)
        dl = self.doc_lens[d]
        norm = self.k1 * (1.0 - self.b + self.b * dl / self.avgdl)
        imp = f / np.maximum(f + norm, 1e-9)
        cells = d // WAND_GRID               # ascending: docs are sorted
        uniq, first = np.unique(cells, return_index=True)
        cmax = np.maximum.reduceat(imp, first) if len(first) else \
            np.zeros(0, np.float64)
        got = (uniq, _RangeMax(cmax))
        self._cache[start] = got
        return got

    def term_cell_ranges(self, start: int, count: int):
        """(c_lo, c_hi) cell range per block of the term at ``start``."""
        got = self._range_cache.get(start)
        if got is None:
            blk = self.block_docs[start : start + count]
            mins = np.maximum(blk[:, 0], 0)
            maxs = np.maximum(blk.max(axis=1), 0)
            got = (mins // WAND_GRID, maxs // WAND_GRID)
            self._range_cache[start] = got
        return got

    def pair_bound(self, start_i: int, count_i: int,
                   start_j: int, count_j: int) -> np.ndarray:
        """Unweighted max impact of term_j's actual postings within each
        of term_i's block doc-ranges (len count_i). Cached per
        (start_i, start_j): zipfian query mixes repeat frequent-term pairs
        constantly, so the per-pair range queries — the dominant host
        planning cost — amortize across the whole query stream."""
        key = (start_i, start_j)
        got = self._pair_cache.get(key)
        if got is not None:
            return got
        c_lo, c_hi = self.term_cell_ranges(start_i, count_i)
        cells_j, table_j = self.term_cells(start_j, count_j)
        lo = np.searchsorted(cells_j, c_lo, side="left")
        hi = np.searchsorted(cells_j, c_hi, side="right") - 1
        has = hi >= lo
        out = np.zeros(count_i, np.float64)
        if has.any():
            out[has] = table_j.query(lo[has], hi[has])
        while len(self._pair_cache) >= self.PAIR_CACHE_CAP:
            self._pair_cache.pop(next(iter(self._pair_cache)))
        self._pair_cache[key] = out
        return out


def build_query_plan(terms_with_weights, term_blocks_fn, block_max_impact,
                     cell_index: Optional[TermCellIndex] = None,
                     k1: float = DEFAULT_K1) -> QueryPlan:
    """Shared host prep for the pruned BM25 path.

    terms_with_weights: [(term, idf*boost)];
    term_blocks_fn(term) -> (start, count) into the block arrays;
    block_max_impact: f32 [n_blocks] (PostingsField.block_max_impact);
    per-block doc ranges come from the cell_index's own cached tables.

    other_ub for a block is the sum, over the query's OTHER terms, of that
    term's max possible contribution among its actual postings within the
    block's doc range (via cell_index) — the aligned block-max WAND bound.
    Cell granularity only loosens the bound (still sound). Without a
    cell_index the bound falls back to the terms' global maxima."""
    per_term = []     # (start, count, weight, bounds)
    for term, weight in terms_with_weights:
        start, count = term_blocks_fn(term)
        if count == 0:
            continue
        impacts = block_max_impact[start : start + count]
        bounds = weight * (k1 + 1.0) * impacts.astype(np.float64)
        per_term.append((start, count, weight, bounds))
    if not per_term:
        return QueryPlan([], [], [], [])

    idx_parts = []
    w_parts = []
    ub_parts = []
    other_parts = []
    for t_i, (start, count, weight, bounds) in enumerate(per_term):
        idx_parts.append(np.arange(start, start + count, dtype=np.int32))
        w_parts.append(np.full(count, weight, np.float32))
        ub_parts.append(bounds)
        o = np.zeros(count, np.float64)
        for t_j, (s_j, cnt_j, w_j, bounds_j) in enumerate(per_term):
            if t_j == t_i:
                continue
            if cell_index is None:
                o += float(bounds_j.max())
                continue
            o += cell_index.pair_bound(start, count, s_j, cnt_j) \
                * (w_j * (k1 + 1.0))
        other_parts.append(o)
    ranges = (np.asarray([t[0] for t in per_term], np.int32),
              np.asarray([t[1] for t in per_term], np.int32),
              np.asarray([t[2] for t in per_term], np.float32))
    return QueryPlan(np.concatenate(idx_parts), np.concatenate(w_parts),
                     np.concatenate(ub_parts), np.concatenate(other_parts),
                     ranges)


class Bm25Executor:
    """Per-(segment, field) BM25 query executor with host-side query prep."""

    def __init__(self, device_postings: DevicePostings, host_postings: PostingsField,
                 total_doc_count: Optional[int] = None):
        self.dev = device_postings
        self.host = host_postings
        # doc count for idf; a coordinator may override with corpus-wide
        # counts (the DFS phase analog, search/dfs/DfsPhase.java:43)
        self.doc_count = total_doc_count or device_postings.n_docs

    def query_weights(self, terms, boost: float = 1.0, df_override=None):
        """(term, idf*boost) pairs; df_override maps term -> corpus-wide df
        (the DFS-phase analog). Falls back to segment-local df per term.
        ``terms`` entries may be plain strings or (term, per_term_boost)
        pairs — the latter carries bool/should per-clause boosts into the
        WAND path."""
        out = []
        for t in terms:
            tb = boost
            if isinstance(t, tuple):
                t, clause_boost = t
                tb = boost * float(clause_boost)
            tid = self.host.terms.get(t)
            df = None
            if df_override is not None:
                df = df_override.get(t)
            if df is None:
                df = int(self.host.doc_freq[tid]) if tid is not None else 0
            if df <= 0 or tid is None:
                continue  # term absent from this segment: no blocks to score
            out.append((t, idf(self.doc_count, df) * tb))
        return out

    def _avgdl(self, avgdl_override=None) -> float:
        """Effective average doc length: a coordinator may override with the
        corpus-wide value (the CollectionStatistics half of the DFS phase —
        search/dfs/DfsPhase.java:43 ships sumTotalTermFreq/docCount so every
        shard norms against the same global avgdl)."""
        if avgdl_override is not None and avgdl_override > 0:
            return float(avgdl_override)
        return float(self.dev.avgdl)

    def scores(self, terms, live: jnp.ndarray, boost: float = 1.0,
               df_override=None, k1: float = DEFAULT_K1, b: float = DEFAULT_B,
               avgdl_override=None) -> jnp.ndarray:
        """Dense masked scores for the query terms (used when composing
        inside bool queries)."""
        tw = self.query_weights(terms, boost, df_override)
        block_idx, block_w = gather_query_blocks(self.host, tw)
        s = bm25_block_scores(self.dev.block_docs, self.dev.block_tfs,
                              jnp.asarray(block_idx), jnp.asarray(block_w),
                              self.dev.doc_lens,
                              jnp.float32(self._avgdl(avgdl_override)),
                              self.dev.n_docs_pad, k1=k1, b=b)
        return jnp.where(live, s, 0.0)

    def top_k(self, terms, live: jnp.ndarray, k: int, boost: float = 1.0,
              df_override=None, k1: float = DEFAULT_K1, b: float = DEFAULT_B,
              avgdl_override=None):
        tw = self.query_weights(terms, boost, df_override)
        block_idx, block_w = gather_query_blocks(self.host, tw)
        return bm25_topk(self.dev.block_docs, self.dev.block_tfs,
                         jnp.asarray(block_idx), jnp.asarray(block_w),
                         self.dev.doc_lens,
                         jnp.float32(self._avgdl(avgdl_override)),
                         live, self.dev.n_docs_pad, k, k1=k1, b=b)

    def top_k_batch(self, queries, live: jnp.ndarray, k: int,
                    boost: float = 1.0, df_override=None,
                    k1: float = DEFAULT_K1, b: float = DEFAULT_B,
                    prune: bool = True, avgdl_override=None,
                    count_hits: bool = False):
        """Batched, block-max-pruned BM25 over Q queries (each a term list).

        Two phases, each ONE device dispatch for the whole batch:
          1. score only each query's P1_BUCKET highest-upper-bound blocks;
             the k-th partial score is a floor (theta) on the true k-th
             score — partial sums only underestimate;
          2. re-score exactly, but only blocks whose WAND bound
             (ub + other-term bounds) clears theta. Zipfian stopword
             blocks never get gathered — this is where the HBM-traffic
             saving is (TopDocsCollectorContext.java:215's block-max WAND
             early termination, re-expressed as static-shape phases).
        Returns (scores [Q, k], doc ids [Q, k]) — plus hits [Q] when
        ``count_hits`` — and records
        last_prune_stats = (blocks_total, blocks_scored)."""
        avgdl = self._avgdl(avgdl_override)
        plans = self.build_plans(queries, boost, df_override, k1, b, avgdl)
        total_blocks = sum(p.n_blocks for p in plans)
        max_blocks = max((p.n_blocks for p in plans), default=1)
        if not prune or max_blocks <= P1_BUCKET:
            # every block is gathered — counts (if asked) are EXACT
            self.last_prune_stats = (total_blocks, total_blocks)
            self.last_hits_exact = True
            return self._dispatch_flat(plans, live, k, k1, b, avgdl,
                                       counted=count_hits)
        p1 = [p.top_by_ub(P1_BUCKET) for p in plans]
        s1, _ = self._dispatch_flat(p1, live, k, k1, b, avgdl)
        theta = np.asarray(s1)[:, k - 1]          # -inf when < k matches
        return self.finish_pruned(plans, theta, live, k, k1, b, avgdl,
                                  count_hits)

    def build_plans(self, queries, boost: float = 1.0, df_override=None,
                    k1: float = DEFAULT_K1, b: float = DEFAULT_B,
                    avgdl: Optional[float] = None):
        """Host planning for a batch: one WAND block plan per query."""
        if avgdl is None:
            avgdl = self._avgdl(None)
        hp = self.host
        # per-term cell index for the aligned WAND bound (within a term,
        # blocks are doc-sorted; entry 0 of every block is always valid).
        # Keyed by (k1, b, avgdl) in a small FIFO-bounded dict so DFS
        # (global avgdl) and plain (segment avgdl) traffic interleave
        # without rebuilding each other's lazily-filled cell tables.
        cells_key = (k1, b, avgdl)
        cells = getattr(self, "_cell_cache", None)
        if cells is None:
            cells = self._cell_cache = {}
        cell_index = cells.get(cells_key)
        if cell_index is None:
            while len(cells) >= 4:
                cells.pop(next(iter(cells)))
            cell_index = cells[cells_key] = TermCellIndex(
                hp.block_docs, hp.block_tfs, hp.doc_lens, avgdl, k1=k1, b=b)
        plans = []
        for terms in queries:
            tw = self.query_weights(terms, boost, df_override)
            plans.append(build_query_plan(
                tw, self.host.term_blocks,
                self.host.block_max_impact(k1, b, avgdl),
                cell_index=cell_index, k1=k1))
        return plans

    def phase1(self, plans, live: jnp.ndarray, k: int,
               k1: float = DEFAULT_K1, b: float = DEFAULT_B,
               avgdl: Optional[float] = None):
        """Dispatch phase 1 (top-ub blocks) and return the DEVICE scores
        [Q, k] without syncing — a multi-segment shard launches every
        segment's phase 1 before blocking once for all thetas."""
        if avgdl is None:
            avgdl = self._avgdl(None)
        p1 = [p.top_by_ub(P1_BUCKET) for p in plans]
        s1, _ = self._dispatch_flat(p1, live, k, k1, b, avgdl)
        return s1

    def finish_pruned(self, plans, theta, live: jnp.ndarray, k: int,
                      k1: float = DEFAULT_K1, b: float = DEFAULT_B,
                      avgdl: Optional[float] = None,
                      count_hits: bool = False):
        """Phase 2: drop blocks whose WAND bound misses theta (one theta
        per query — possibly a shard-global one tighter than this
        segment's own) and score the survivors exactly."""
        if avgdl is None:
            avgdl = self._avgdl(None)
        total_blocks = sum(p.n_blocks for p in plans)
        p2 = [p.survivors(float(t)) for p, t in zip(plans, theta)]
        scored = sum(p.n_blocks for p in p2)
        p1_cost = sum(min(p.n_blocks, P1_BUCKET) for p in plans)
        self.last_prune_stats = (total_blocks,
                                 min(scored + p1_cost, total_blocks))
        # pruned counts observe only survivor blocks: a LOWER bound
        self.last_hits_exact = scored >= total_blocks
        return self._dispatch_flat(p2, live, k, k1, b, avgdl,
                                   counted=count_hits)

    # per-dispatch ceiling on the query dimension: the score plane is
    # n_q * n_docs_pad f32 — 64 queries over a 16M-doc pad is 4GB, so
    # bigger batches split (and phase-1 theta syncs once per chunk)
    MAX_CHUNK_Q = 64

    def _dispatch_flat(self, plans, live, k, k1, b, avgdl, counted=False):
        return dispatch_flat(self.dev.block_docs, self.dev.block_tfs,
                             self.dev.doc_lens, self.dev.n_docs_pad,
                             plans, live, k, k1, b, avgdl=avgdl,
                             counted=counted)


MAX_CHUNK_Q = Bm25Executor.MAX_CHUNK_Q


def dispatch_flat(block_docs, block_tfs, doc_lens, n_docs_pad: int,
                  plans, live, k: int, k1: float, b: float,
                  avgdl: Optional[float] = None,
                  block_avgdl: Optional[np.ndarray] = None,
                  counted: bool = False, counter: Optional[list] = None,
                  count_segments: Optional[Tuple] = None):
    """Flat-dispatch a batch of plans over one block store: device work
    scales with the ACTUAL total block count (one pow-ladder bucket of
    padding), never with Q x max-plan as the padded layout did. Chunks
    bound both the gather temp (MAX_BATCH_CELLS) and the score plane
    (MAX_CHUNK_Q); n_q pads to a pow2 bucket so shapes stay bucketed.

    The block store is either one segment's (scalar ``avgdl``) or a whole
    shard plane's (``block_avgdl`` [NB] host array, gathered per plan so
    every block keeps its owning segment's norm). ``counter``, when given,
    accumulates the number of device programs launched (bench/stats
    observability for dispatches-per-query).

    ``count_segments``: (seg_ids device [n_docs_pad] int32, n_segs) —
    hits come back PER SEGMENT [n_q, n_segs] instead of [n_q] (the
    totals-disabled plane contract); overrides ``counted``."""
    chunks: list = []
    cur: list = []
    cells = 0
    for p in plans:
        nb = max(p.n_blocks, 1)
        if cur and (len(cur) >= MAX_CHUNK_Q
                    or cells + nb > MAX_BATCH_CELLS):
            chunks.append(cur)
            cur, cells = [], 0
        cur.append(p)
        cells += nb
    if cur:
        chunks.append(cur)
    if count_segments is not None:
        counted = True
    out_s, out_d, out_h = [], [], []
    for chunk in chunks:
        n_real = len(chunk)
        n_q = next_pow2(n_real, minimum=1)
        fb = qb_bucket(max(sum(p.n_blocks for p in chunk), 1))
        idx, w, qid = flatten_plans(chunk, fb)
        if block_avgdl is not None:
            flat_avg = block_avgdl[idx].astype(np.float32)
        else:
            flat_avg = np.full(fb, avgdl, np.float32)
        if counter is not None:
            counter.append(1)
        record_dispatch()
        if count_segments is not None:
            seg_ids, n_segs = count_segments
            got = _bm25_flat_kernel_seg(
                block_docs, block_tfs,
                jnp.asarray(idx), jnp.asarray(w), jnp.asarray(qid),
                doc_lens, jnp.asarray(flat_avg), live, seg_ids,
                n_docs_pad, n_q, k, k1=k1, b=b, n_segs=n_segs)
        else:
            # ALWAYS the counted program: hits are one cheap reduction
            # off the score plane the kernel materializes anyway. The
            # counted flag used to flip with BATCH COMPOSITION (phase A
            # counts only when an exact-mode member rides along), so
            # real serving compiled both variants of each (FB, n_q, k)
            # — half of them pure compile-cache waste. One variant
            # keeps a warm serving pattern in single-digit buckets.
            got = bm25_topk_flat_counted(
                block_docs, block_tfs,
                jnp.asarray(idx), jnp.asarray(w), jnp.asarray(qid),
                doc_lens, jnp.asarray(flat_avg), live,
                n_docs_pad, n_q, k, k1=k1, b=b)
        if len(chunks) == 1:
            s, d, h = got
            if counted:
                return s[:n_real], d[:n_real], to_host(h)[:n_real]
            return s[:n_real], d[:n_real]
        s, d, h = got
        if counted:
            s, d, h = to_host(s, d, h)
            out_h.append(h[:n_real])
        else:
            s, d = to_host(s, d)
        out_s.append(s[:n_real])
        out_d.append(d[:n_real])
    s = jnp.asarray(np.concatenate(out_s))
    d = jnp.asarray(np.concatenate(out_d))
    if counted:
        return s, d, np.concatenate(out_h)
    return s, d
