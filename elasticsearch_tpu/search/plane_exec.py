"""Shard-plane execution: whole-shard device programs over the packed
multi-segment plane (ops/device_segment.py PlanePart family).

One function per query class, each the fused counterpart of the
per-segment loops in search/phase.py (solo) and search/batch_executor.py
(batched) — BOTH paths call into here when a plane is resident, so solo
and batched serving share one implementation. Exact classes (text,
exact/filtered kNN, sparse) reproduce the per-segment results
identically; the quantized kNN coarse pass is exact up to its re-rank
depth by contract (search.plane.quantized: false forces full exactness),
and ANN routing decisions are made to agree with the per-segment
fallback so plane residency never flips an exact result approximate:

- ``plane_wand_topk``: Q text queries through the block-max-pruned BM25
  path in TWO device dispatches for the whole shard (phase 1 theta, phase
  2 survivors) instead of two per segment; per-block avgdl keeps the
  per-segment length norms exact.
- ``plane_knn_winners``: Q kNN queries (filtered or not) in ONE matmul
  over the stacked vector plane — optionally int8-coarse + exact-f32
  re-rank (the quantized scoring pass) — or ONE shard-level IVF probe,
  with the per-segment demux reduced to a host-side offset translation.
- ``plane_sparse_topk``: Q resolved expansions in ONE gather/scatter over
  the stacked rank_features blocks, exact counts off the score plane.

Every function degrades by construction: callers treat a None plane (or
``PlaneFallback``) as "run the existing per-segment path".
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from elasticsearch_tpu.index.segment import next_pow2
from elasticsearch_tpu.ops.bm25 import (
    DEFAULT_B, DEFAULT_K1, P1_BUCKET, QueryPlan, dispatch_flat,
)
from elasticsearch_tpu.ops.device_segment import (
    MESH_PLANES, PLANES, PlaneVectors,
)
from elasticsearch_tpu.search import telemetry
from elasticsearch_tpu.search.phase import ShardDoc


class PlaneFallback(Exception):
    """This batch cannot run on the plane (e.g. IVF-routed members whose
    num_candidates imply different probe widths); members take the
    per-segment path."""


def _coarse_attempt(cls: str, n_q: int, attempt: Callable[[], Any]):
    """Measured-latency engage rule shared by every coarse-tier class:
    the corpus-size gate decides whether coarse CAN engage; this decides
    whether it SHOULD, from the observed per-query serve latencies. When
    the coarse EWMA for this class runs decisively slower than the exact
    EWMA (re-rank escalations eating the bf16/int8 savings), the class
    disengages — counted ``quantized_disengaged_slow`` — and every 32nd
    query probes coarse again so a shifted workload can re-engage."""
    if not PLANES.quantized_engaged(cls):
        return None
    t0 = time.monotonic()
    got = attempt()
    if got is not None:
        PLANES.note_tier_latency(cls, "coarse",
                                 (time.monotonic() - t0) / max(n_q, 1))
    return got


def _note_exact(cls: str, n_q: int, t0: float) -> None:
    """The exact tier's side of the same comparison — recorded only while
    the quantized tier is configured on (with it off there is nothing to
    disengage), so both EWMAs describe the same workload."""
    if PLANES.quantized:
        PLANES.note_tier_latency(cls, "exact",
                                 (time.monotonic() - t0) / max(n_q, 1))


# ---------------------------------------------------------------------------
# adaptive re-rank depth: the margin rule shared by every coarse tier
# ---------------------------------------------------------------------------

# a-priori per-doc coarse error, relative to the coarse score. bf16
# classes: every contribution is a product/quotient chain of
# bf16-rounded operands (<= ~8 ulps ~ 0.031 relative) summed with f32
# accumulation over strictly positive terms, so 0.04 bounds ANY doc's
# deviation — included or excluded — and the margin below is a real
# proof for bm25/sparse. int8 kNN has no usable closed-form bound (it
# loosens with D and amax/rms), so 0.02 hardens the empirical estimate
# and the escalate-then-serve-exact backstop owns the tail.
REL_BF16 = 0.04
REL_INT8 = 0.02


def _margin_ok(s_k: float, c_cut: float, eps: float, rel: float) -> bool:
    """True when the coarse pass provably kept the true top-k.

    Any EXCLUDED doc's coarse score is <= ``c_cut`` (the k'-th coarse
    score), so its exact score is <= c_cut plus its coarse error. The
    error is bounded two ways at once: ``eps`` is the max observed
    |exact - coarse| among the re-ranked candidates (doubled for
    safety), and ``rel`` is the class's a-priori relative bound
    (REL_BF16 / REL_INT8). When the exact k-th score clears both, no
    excluded doc can enter the served top-k; when it cannot — including
    exact-score ties straddling the cut — the caller deepens k' and
    re-dispatches, bounded by ``search.plane.rerank_depth_max``, past
    which the EXACT path serves (typed fallback): golden parity is an
    invariant, not a tuning goal."""
    if not np.isfinite(c_cut):
        return True     # fewer matches than k': nothing was excluded
    if not np.isfinite(s_k):
        return False
    return (s_k - c_cut) > (2.0 * eps + rel * abs(c_cut) + 1e-6)


def _coarse_depth0(k: int, n_docs_pad: int) -> int:
    return min(max(int(PLANES.rerank_depth), k), n_docs_pad)


def _adaptive_depths(k: int, n_docs_pad: int):
    """Yield (kprime, is_last) re-rank depths for the adaptive loop:
    the configured starting depth, doubling per escalation up to
    ``search.plane.rerank_depth_max`` (or full plane coverage, where
    nothing can be excluded). Resuming the generator IS the escalation
    — it counts ``rerank_escalations`` — so every coarse tier shares
    one depth/bookkeeping discipline; the caller serves and breaks on
    a clean margin, and falls back to exact when ``is_last`` still
    cannot prove parity."""
    depth = _coarse_depth0(k, n_docs_pad)
    max_depth = max(int(PLANES.rerank_depth_max), depth)
    while True:
        kprime = min(depth, n_docs_pad)
        yield kprime, (kprime >= n_docs_pad or depth >= max_depth)
        PLANES.stats["rerank_escalations"] += 1
        depth = min(depth * 2, max_depth)


def _count_plane_quantized_fallback() -> None:
    PLANES.stats["quantized_exact_fallbacks"] += 1
    telemetry.TELEMETRY.count_fallback(telemetry.PLANE_QUANTIZED_FALLBACK)


def _reader_of(ctxs):
    return ctxs[0].reader


def _live_host(reader) -> np.ndarray:
    return np.concatenate([np.asarray(m) for m in reader.live_masks]) \
        if reader.live_masks else np.zeros(0, bool)


# ---------------------------------------------------------------------------
# text: block-max pruned BM25 over the postings plane
# ---------------------------------------------------------------------------

def _coarse_wand_topk(part, per_seg, has_terms, n_q: int, live,
                      eff_block_avgdl, k_plane: int, want: int,
                      track_limit: int, counts_on: bool,
                      check_members: Optional[Callable[[], None]],
                      counter: Optional[list]) -> Optional[List[Tuple]]:
    """The quantized two-tier text path: ONE bf16 coarse dispatch over
    the FULL (unpruned) plans — no WAND host planning, no theta sync, no
    recount — plus ONE exact f32 re-rank that looks the top-k'
    candidates up in each query's term ranges (``rerank_lookups``
    counts the lookups), with the adaptive-depth margin loop. The
    coarse dispatch reads each query's head terms from the plane's
    dense head mirror and scatters the rest (``coarse_dense_terms``,
    ``coarse_scatter_blocks``; ``coarse_dense_refused`` counts drains
    that scattered everything because the breaker refused it). Totals
    come EXACT off the coarse pass's per-segment counts (both the
    counts-then-skip and the totals-disabled contracts), so no mode
    needs a second counting dispatch. Returns plane_wand_topk's
    per-member tuples, or None when the exact phases must serve (corpus
    below the engage threshold, batch too large for one dispatch, mirror
    refused, margin exhausted — the latter two typed
    plane_quantized_fallback)."""
    from elasticsearch_tpu.ops.bm25 import (
        MAX_BATCH_CELLS, MAX_CHUNK_Q, _bm25_coarse_kernel,
        _bm25_rerank_kernel, dense_slots, flatten_plans, qb_bucket,
        rerank_ranges, tail_sizes,
    )
    depth0 = _coarse_depth0(k_plane, part.n_docs_pad)
    if part.n_docs_total <= 4 * depth0:
        return None
    offsets = [bb for _c, _p, bb in per_seg]
    rows = [QueryPlan.concat([p[qi] for _c, p, _bb in per_seg],
                             idx_offsets=offsets) for qi in range(n_q)]
    cells = sum(p.n_blocks for p in rows)
    if n_q > MAX_CHUNK_Q or cells > MAX_BATCH_CELLS:
        return None     # chunked batches keep the exact phased path
    mirror = part.quantized_mirror()
    if mirror is None:
        _count_plane_quantized_fallback()
        return None
    tf16, dl16 = mirror
    n_q_pad = next_pow2(max(n_q, 1), minimum=1)
    n_segs = len(part.segments)
    heads = part.head_mirror()
    tails, dense, n_dense = rows, None, 0
    if heads is not None:
        tails, slot_rows, slot_w, n_dense = dense_slots(rows, heads,
                                                        n_q_pad)
        # each segment's effective avgdl, DFS override included
        seg_avg = np.ones(n_segs, np.float32)
        for pos, _pf, block_base, _avgdl in part.refs:
            seg_avg[pos] = eff_block_avgdl[block_base]
        dense = (heads.cols, jnp.asarray(slot_rows), jnp.asarray(slot_w),
                 jnp.asarray(seg_avg))
    elif part.heads_refused:
        PLANES.stats["coarse_dense_refused"] += 1
    n_tail = sum(p.n_blocks for p in tails)
    # the bucket of every block the rows read, as without the mirror: the
    # kernel scatters the smallest prefix of it that holds the tail
    fb = qb_bucket(max(cells, 1))
    idx, w, qid = flatten_plans(tails, fb)
    tail = np.int32(np.searchsorted(tail_sizes(fb), n_tail))
    flat_avg = eff_block_avgdl[idx].astype(np.float32)
    idx_dev = jnp.asarray(idx)
    w_dev = jnp.asarray(w)
    qid_dev = jnp.asarray(qid)
    favg_dev = jnp.asarray(flat_avg)
    ranges_dev = [jnp.asarray(a) for a in
                  rerank_ranges(rows, n_q_pad, eff_block_avgdl)]
    n_ranges = sum(len(p.ranges[0]) for p in rows)
    seg_ids = part.seg_ids()
    blocks_total = [rows[qi].n_blocks for qi in range(n_q)]

    from elasticsearch_tpu.indices.breaker import BREAKERS
    for kprime, last in _adaptive_depths(k_plane, part.n_docs_pad):
        # one region per depth step: the trace shows how far each drain
        # escalated
        with telemetry.region("plane.rerank", kprime=kprime):
            if check_members is not None:
                check_members()
            if counter is not None:
                counter.extend((1, 1))
            telemetry.record_dispatch(2)
            PLANES.stats["rerank_lookups"] += n_ranges * kprime
            PLANES.stats["coarse_dense_terms"] += n_dense
            PLANES.stats["coarse_scatter_blocks"] += n_tail
            # the coarse pass's f32 score plane and its masks
            transient = 8 * part.n_docs_pad * n_q_pad
            with BREAKERS.breaker("request").limit_scope(
                    transient, "plane_coarse_wand"):
                cs, cand, hits = _bm25_coarse_kernel(
                    part.block_docs, tf16, idx_dev, w_dev, qid_dev, dl16,
                    favg_dev, live, seg_ids, part.n_docs_pad, n_q_pad,
                    n_segs, kprime, k1=DEFAULT_K1, b=DEFAULT_B,
                    tail=tail, dense=dense)
                s, d, eps = _bm25_rerank_kernel(
                    part.block_docs, part.block_tfs, *ranges_dev,
                    part.doc_lens, live, cand, cs, k_plane,
                    k1=DEFAULT_K1, b=DEFAULT_B)
            cs_h, s_h, eps_h = telemetry.to_host(cs, s, eps)
            k_last = min(k_plane, s_h.shape[1]) - 1
            clean = all(_margin_ok(float(s_h[qi, k_last]),
                                   float(cs_h[qi, kprime - 1]),
                                   float(eps_h[qi]), REL_BF16)
                        for qi in range(n_q) if has_terms[qi])
        if clean:
            break
        if last:
            _count_plane_quantized_fallback()
            return None

    hits_h, d_h = telemetry.to_host(hits, d)
    PLANES.note_quantized(kprime, sum(1 for qi in range(n_q)
                                      if has_terms[qi]))
    empty = ([], 0, "eq", None, (0, 0))
    out: List[Tuple] = []
    for qi in range(n_q):
        if not has_terms[qi]:
            out.append(empty)
            continue
        s_row, d_row = s_h[qi], d_h[qi]
        finite = s_row != -np.inf
        si, local = part.demux(d_row[finite])
        candidates = [ShardDoc(int(a), int(b), float(sc), (float(sc),))
                      for a, b, sc in zip(si, local, s_row[finite])]
        candidates.sort(key=lambda c: (-c.score, c.segment_idx, c.doc))
        max_score = max((c.score for c in candidates), default=None)
        # the coarse pass gathered every block (the re-rank only looks
        # candidates up): no pruning
        prune = (blocks_total[qi], blocks_total[qi])
        h_row = hits_h[qi]
        if not counts_on:
            # totals disabled: the per-segment clipped contract, read
            # off the coarse pass's exact per-segment counts
            total = int(np.minimum(h_row, want).sum())
            out.append((candidates, total, "gte", max_score, prune))
            continue
        hits_all = int(h_row.sum())
        if hits_all >= track_limit:
            out.append((candidates, track_limit, "gte", max_score,
                        prune))
        else:
            out.append((candidates, hits_all, "eq", max_score, prune))
    return out


def plane_wand_topk(ctxs, part, field: str,
                    clause_lists: List[List[Tuple[str, float]]],
                    want: int, track_limit: int,
                    check_members: Optional[Callable[[], None]] = None,
                    counter: Optional[list] = None) -> Optional[List[Tuple]]:
    """Q queries through the pruned BM25 path with the whole shard's
    postings in one block store. Member-for-member identical semantics to
    the per-segment loops (scores, candidates, counts-then-skip totals);
    per-member theta comes from that member's own phase-1 partials over
    ALL segments at once, so segments prune each other exactly as the
    shard-global theta barrier did — without the per-segment dispatches.

    Returns per member (candidates, hits, relation, max_score,
    (blocks_total, blocks_scored)).

    Totals-disabled requests (track_limit <= 0) are served too: the
    per-segment contract reports "candidates found" with PER-SEGMENT
    truncation (sum of min(matches, want) per segment), which the fused
    top-k cannot reproduce from a whole-plane count — so the final
    scoring dispatch counts per segment (``part.seg_ids`` channel) and
    the host clips each segment's count at the collection window.

    DFS-normed requests (a corpus-wide avgdl override) are served by the
    second normalization channel: per-doc lengths live on the plane, the
    per-block avgdl rides the dispatch as a kernel argument — an
    override simply replaces the baked per-segment values with the
    corpus-wide one, for plan upper bounds AND the length norm alike."""
    from elasticsearch_tpu.search.execute import _bm25_planner
    telemetry.mark_plane_served()
    counts_on = track_limit > 0
    n_q = len(clause_lists)
    reader = _reader_of(ctxs)

    avgdl_override = None
    per_seg = []        # (ctx, plans[n_q], block_base)
    seen_terms: List[Dict[str, float]] = [{} for _ in range(n_q)]
    has_terms = [False] * n_q
    for pos, pf, block_base, avgdl in part.refs:
        ctx = ctxs[pos]
        override = ctx.avgdl_for(field)
        if override is not None:
            # DFS-normed: every segment norms against the corpus-wide
            # avgdl (it is per-request per-field, so one value for all)
            avgdl_override = float(override)
            avgdl = avgdl_override
        analyzer = ctx.search_analyzer(field)
        ex = _bm25_planner(ctx, field)
        if ex is None:
            continue
        df_map = ctx.df_for(field) or {}
        member_terms: List[List[Tuple[str, float]]] = []
        any_terms = False
        for qi, clauses in enumerate(clause_lists):
            terms: List[Tuple[str, float]] = []
            for text, boost in clauses:
                terms.extend((t, boost) for t in analyzer.terms(text))
            member_terms.append(terms)
            if terms:
                any_terms = True
                has_terms[qi] = True
                for t, _b in terms:
                    if t not in seen_terms[qi]:
                        seen_terms[qi][t] = float(df_map.get(t, 0))
        if not any_terms:
            continue
        plans = ex.build_plans(member_terms, df_override=df_map or None,
                               avgdl=avgdl)
        per_seg.append((ctx, plans, block_base))

    empty = ([], 0, "eq", None, (0, 0))
    if not per_seg:
        return [empty] * n_q

    live = part.live_mask(reader.live_masks)
    k_plane = min(max(want, 1), part.n_docs_pad)
    empty_plan = QueryPlan([], [], [], [])

    hits_upper = [int(sum(s.values())) for s in seen_terms]
    exact_mode = [counts_on and hits_upper[qi] <= track_limit
                  for qi in range(n_q)]
    # the second normalization channel: per-block avgdl is a DISPATCH
    # argument, so a DFS override replaces the baked per-segment values
    eff_block_avgdl = part.block_avgdl if avgdl_override is None else \
        np.full_like(part.block_avgdl, avgdl_override)

    # quantized coarse tier (search.plane.quantized): bf16 coarse pass
    # over the full plans + exact f32 re-rank with adaptive depth — the
    # kNN two-tier pattern generalized to the scatter-bound text class;
    # None = serve the exact phased path below (typed when it is a
    # fallback rather than a sizing decision)
    if PLANES.quantized:
        got = _coarse_attempt("bm25", n_q, lambda: _coarse_wand_topk(
            part, per_seg, has_terms, n_q, live, eff_block_avgdl,
            k_plane, want, track_limit, counts_on, check_members,
            counter))
        if got is not None:
            return got
    t_exact = time.monotonic()

    def _dispatch(rows, k, counted, count_segments=None):
        if check_members is not None:
            check_members()
        # the scatter materializes a [chunk_q, n_docs_pad] f32 score
        # plane sized to the WHOLE shard — charge the request breaker for
        # it (score plane + top-k temporaries) so an over-budget plane
        # dispatch 429s instead of OOMing the chip
        from elasticsearch_tpu.indices.breaker import BREAKERS
        from elasticsearch_tpu.ops.bm25 import MAX_CHUNK_Q
        transient = 8 * part.n_docs_pad * min(max(len(rows), 1),
                                              MAX_CHUNK_Q)
        with BREAKERS.breaker("request").limit_scope(
                transient, "plane_wand_topk"):
            return dispatch_flat(part.block_docs, part.block_tfs,
                                 part.doc_lens, part.n_docs_pad, rows,
                                 live, k, DEFAULT_K1, DEFAULT_B,
                                 block_avgdl=eff_block_avgdl,
                                 counted=counted, counter=counter,
                                 count_segments=count_segments)

    # phase A — ONE dispatch for the whole shard: exact-mode members score
    # every block (counted; final), pruned members their per-segment
    # P1_BUCKET highest-upper-bound blocks (the same block set the
    # per-segment path's phase 1 gathers)
    rows_a = []
    for qi in range(n_q):
        segs = [p[qi] if exact_mode[qi] else p[qi].top_by_ub(P1_BUCKET)
                for _ctx, p, _bb in per_seg]
        rows_a.append(QueryPlan.concat(
            segs, idx_offsets=[bb for _c, _p, bb in per_seg]))
    counted_a = any(exact_mode)
    got_a = _dispatch(rows_a, k_plane, counted_a)
    if counted_a:
        s_a, d_a, h_a = got_a
    else:
        s_a, d_a = got_a
        h_a = None
    s_a_host = telemetry.to_host(s_a)

    theta = np.full(n_q, -np.inf)
    for qi in range(n_q):
        if exact_mode[qi]:
            continue
        finite = s_a_host[qi][np.isfinite(s_a_host[qi])]
        if len(finite) >= want:
            theta[qi] = float(np.sort(finite)[-want])

    # phase B — ONE dispatch: pruned members' WAND survivors scored
    # exactly (+ counted); exact members ride as empty rows. In
    # totals-disabled mode the dispatch counts PER SEGMENT so the host
    # can reproduce the per-segment truncated "candidates found" totals.
    blocks_total = [0] * n_q
    blocks_scored = [0] * n_q
    hits_exact = [True] * n_q
    need_b = not all(exact_mode)
    rows_b = []
    for qi in range(n_q):
        segs = []
        for _ctx, plans, _bb in per_seg:
            p = plans[qi]
            if exact_mode[qi]:
                blocks_total[qi] += p.n_blocks
                blocks_scored[qi] += p.n_blocks
                segs.append(empty_plan)
                continue
            surv = p.survivors(float(theta[qi]))
            p1_cost = min(p.n_blocks, P1_BUCKET)
            blocks_total[qi] += p.n_blocks
            blocks_scored[qi] += min(surv.n_blocks + p1_cost, p.n_blocks)
            hits_exact[qi] = hits_exact[qi] and surv.n_blocks >= p.n_blocks
            segs.append(surv)
        rows_b.append(QueryPlan.concat(
            segs, idx_offsets=[bb for _c, _p, bb in per_seg]))
    if need_b:
        if counts_on:
            s_b, d_b, h_b = _dispatch(rows_b, k_plane, True)
        else:
            s_b, d_b, h_b = _dispatch(
                rows_b, k_plane, False,
                count_segments=(part.seg_ids(), len(part.segments)))
    else:
        s_b = d_b = h_b = None
    # the results the demux reads, fetched once each (counted dispatches
    # return their hits on the host already)
    if any(exact_mode[qi] and has_terms[qi] for qi in range(n_q)):
        d_a = telemetry.to_host(d_a)
    if need_b and any(not exact_mode[qi] and has_terms[qi]
                      for qi in range(n_q)):
        s_b, d_b = telemetry.to_host(s_b, d_b)

    out: List[Tuple] = []
    for qi in range(n_q):
        if not has_terms[qi]:
            out.append(empty)
            continue
        if exact_mode[qi]:
            s_row, d_row = s_a_host[qi], d_a[qi]
            hits_seen = int(np.asarray(h_a)[qi]) if h_a is not None else 0
        else:
            s_row, d_row = s_b[qi], d_b[qi]
            hits_seen = (int(np.asarray(h_b)[qi].sum())
                         if h_b is not None else 0)
        finite = s_row != -np.inf
        si, local = part.demux(d_row[finite])
        candidates = [ShardDoc(int(s_i), int(d_i), float(sc), (float(sc),))
                      for s_i, d_i, sc in zip(si, local, s_row[finite])]
        candidates.sort(key=lambda c: (-c.score, c.segment_idx, c.doc))
        max_score = max((c.score for c in candidates), default=None)
        prune = (blocks_total[qi], blocks_scored[qi])
        if not counts_on:
            # totals disabled: per-segment "candidates found", each
            # segment's observed matches truncated at the collection
            # window — sum of min(matches, want) per segment, exactly
            # the per-segment path's len(candidates)
            h_row = np.asarray(h_b)[qi]
            total = int(np.minimum(h_row, want).sum())
            out.append((candidates, total, "gte", max_score, prune))
        elif hits_seen >= track_limit:
            out.append((candidates, track_limit, "gte", max_score, prune))
        elif hits_exact[qi] or exact_mode[qi]:
            out.append((candidates, hits_seen, "eq", max_score, prune))
        else:
            out.append((candidates, None, None, max_score, prune))

    # members whose pruned counts might hide hits: one exact unpruned
    # counted pass (k=1; scores already final) — still ONE dispatch
    recount = [qi for qi in range(n_q) if counts_on and out[qi][1] is None]
    if recount:
        rows_r = []
        for qi in range(n_q):
            if qi in recount:
                rows_r.append(QueryPlan.concat(
                    [p[qi] for _c, p, _bb in per_seg],
                    idx_offsets=[bb for _c, _p, bb in per_seg]))
            else:
                rows_r.append(empty_plan)
        _s, _d, h_r = _dispatch(rows_r, 1, True)
        h_r = np.asarray(h_r)
        for qi in recount:
            candidates, _, _, max_score, prune = out[qi]
            exact_hits = int(h_r[qi])
            # >= so the relation at count == track_limit is "gte" on
            # EVERY path — exact-mode and observed-full members already
            # report "gte" there, and the quantized coarse tier (exact
            # counts, no pruning visibility) must be byte-identical to
            # whichever branch the exact path would have taken
            if exact_hits >= track_limit:
                out[qi] = (candidates, track_limit, "gte", max_score,
                           prune)
            else:
                out[qi] = (candidates, exact_hits, "eq", max_score, prune)
    _note_exact("bm25", n_q, t_exact)
    return out


# ---------------------------------------------------------------------------
# kNN: one matmul (or one shard-level IVF probe) over the vector plane
# ---------------------------------------------------------------------------

def plane_ann_route(ctx0, part: PlaneVectors, field: str, k: int,
                    num_candidates: int) -> Optional[Tuple]:
    """Shard-level IVF routing over the plane — the plane analog of
    execute.ann_segment_route, shared by the solo kNN rewrite and the
    batched executor so their ANN results are identical by construction.
    None = exact plane path; else (index, rows, oversample, nprobe) with
    index None when the field holds no vectors at all."""
    from elasticsearch_tpu.search.execute import ANN_DEFAULT_MIN_DOCS
    mapper = ctx0.mappers.mapper(field)
    opts = getattr(mapper, "index_options", None) or {}
    wants_ivf = opts.get("type") == "ivf"
    if opts.get("type") not in (None, "ivf"):
        return None
    if not wants_ivf:
        # auto-sizing must agree with the per-segment fallback's routing
        # decision, or plane residency would silently flip EXACT results
        # to approximate ones: take the shard-level IVF only when every
        # vector-bearing segment would take the per-segment IVF anyway
        sizes = [s.n_docs for s in part.segments
                 if s.vectors.get(field) is not None]
        if not sizes or min(sizes) < ANN_DEFAULT_MIN_DOCS:
            return None
    from elasticsearch_tpu.utils.errors import CircuitBreakingError
    try:
        index, rows = part.ivf_index(opts.get("nlist"))
    except CircuitBreakingError:
        # over budget: the exact plane path serves
        telemetry.TELEMETRY.count_fallback(
            telemetry.PLANE_IVF_BREAKER_REFUSED)
        return None
    if index is None:
        return (None, rows, 0, 0)
    oversample = min(max(2 * k, k + 16), len(rows))
    nprobe = opts.get("nprobe") or max(
        1, int(np.ceil(num_candidates / max(index.list_len, 1))))
    return (index, rows, oversample, nprobe)


def _probe_plane(index, queries: np.ndarray, k: int, nprobe: int,
                 rows: np.ndarray, live_host: np.ndarray, part,
                 oversample: int) -> List[List[Tuple[int, int, float]]]:
    """Batched shard-IVF probe + the host-side demux: list-row ids map to
    plane docs through ``rows``, deleted docs drop out post-probe, plane
    docs translate to (segment_idx, local_doc) through the base offsets."""
    scores, ids = index.search(np.asarray(queries, np.float32),
                               oversample, nprobe=nprobe)
    out = []
    for qi in range(scores.shape[0]):
        valid = ids[qi] >= 0
        docs = rows[ids[qi][valid]]
        alive = (docs < len(live_host)) & live_host[
            np.minimum(docs, max(len(live_host) - 1, 0))]
        docs, kept = docs[alive], scores[qi][valid][alive]
        si, local = part.demux(docs)       # one vectorized demux per query
        hits = [(int(a), int(b), float(s))
                for a, b, s in zip(si[:k], local[:k], kept[:k])]
        out.append(hits)
    return out


def _filter_mask_rows(ctxs, part, specs, exact_idx) -> Tuple[Any, bool]:
    """Per-member plane filter masks: each DISTINCT filter executes once
    per segment (the solo path's filter-context mask builders) and its
    per-segment masks stack into plane doc space. Returns (masks, shared):
    masks None (no filters), a [N_pad] jnp mask (every member agrees — the
    autocomplete / faceted-nav shape), or a [B, N_pad] np stack."""
    from elasticsearch_tpu.search.execute import execute as execute_query
    fkeys = {specs[qi].filter_key for qi in exact_idx}
    if fkeys == {None}:
        return None, False
    by_key: Dict[Optional[str], np.ndarray] = {}
    for qi in exact_idx:
        s_qi = specs[qi]
        if s_qi.filter is None or s_qi.filter_key in by_key:
            continue
        row = np.zeros(part.n_docs_pad, bool)
        for pos, ctx in enumerate(ctxs):
            _, fmask = execute_query(s_qi.filter, ctx)
            base = int(part.doc_base[pos])
            n = ctx.segment.n_docs
            row[base: base + n] = np.asarray(fmask)[:n]
        by_key[s_qi.filter_key] = row
    if len(fkeys) == 1:
        return jnp.asarray(by_key[next(iter(fkeys))]), True
    rows = np.ones((len(exact_idx), part.n_docs_pad), bool)
    for r, qi in enumerate(exact_idx):
        fk = specs[qi].filter_key
        if fk is not None:
            rows[r] = by_key[fk]
    return rows, False


def _quantized_topk(part: PlaneVectors, vectors: np.ndarray, live,
                    masks, k: int, counter: Optional[list] = None):
    """int8 coarse pass over the full plane + exact f32 re-rank of the
    top-k' candidates, with ADAPTIVE depth: the margin at position k'
    (via the re-rank's observed coarse error) must prove the true top-k
    survived, else the pass deepens x2 and re-dispatches up to
    ``search.plane.rerank_depth_max`` — past which None is returned and
    the exact path serves (typed plane_quantized_fallback). Also None
    when the quantized mirror is unavailable (breaker) or the corpus is
    too small for the coarse pass to pay."""
    depth0 = _coarse_depth0(k, part.n_docs_pad)
    if part.n_docs_total <= 4 * depth0:
        return None         # coarse+rerank would cost more than exact
    mirror = part.quantized_mirror()
    if mirror is None:
        _count_plane_quantized_fallback()
        return None
    q8, scales = mirror
    from elasticsearch_tpu.ops.knn import (
        knn_coarse_candidates, knn_coarse_candidates_masked,
        knn_rerank_exact, knn_rerank_exact_masked, pad_mask_rows_pow2,
        pad_queries_pow2,
    )
    q_host, n_real = pad_queries_pow2(vectors)
    allowed = live & part.exists
    queries = jnp.asarray(q_host)
    m_dev = None
    if masks is not None and getattr(masks, "ndim", 1) == 2:
        m_dev = jnp.asarray(pad_mask_rows_pow2(masks, q_host.shape[0]))
    elif masks is not None:
        allowed = allowed & masks       # shared filter mask
    for kprime, last in _adaptive_depths(k, part.n_docs_pad):
        if counter is not None:
            counter.extend((1, 1))
        telemetry.record_dispatch(2)      # coarse pass + exact re-rank
        if m_dev is not None:
            cs, cand = knn_coarse_candidates_masked(
                q8, scales, part.norms, allowed, queries, m_dev, kprime,
                part.similarity)
            s, d, eps = knn_rerank_exact_masked(
                part.matrix, part.norms, allowed, queries, cand, cs,
                m_dev, k, part.similarity)
        else:
            cs, cand = knn_coarse_candidates(q8, scales, part.norms,
                                             allowed, queries, kprime,
                                             part.similarity)
            s, d, eps = knn_rerank_exact(part.matrix, part.norms,
                                         allowed, queries, cand, cs, k,
                                         part.similarity)
        cs_h = np.asarray(cs)
        s_h = np.asarray(s)
        eps_h = np.asarray(eps)
        k_last = min(k, s_h.shape[1]) - 1
        if all(_margin_ok(float(s_h[row, k_last]),
                          float(cs_h[row, kprime - 1]),
                          float(eps_h[row]), REL_INT8)
               for row in range(n_real)):
            PLANES.note_quantized(kprime, n_real)
            return s[:n_real], d[:n_real]
        if last:
            _count_plane_quantized_fallback()
            return None


def maps_exact(mappers, field: str) -> bool:
    """A field mapped ``index_options: {type: flat}`` asks for exact
    brute force at any size: neither the IVF probe nor the int8 coarse
    tier (whose margin is empirical) may serve it."""
    opts = getattr(mappers.mapper(field), "index_options", None) or {}
    return opts.get("type") == "flat"


def plane_knn_winners(ctxs, part: PlaneVectors, field: str, specs,
                      k: int,
                      check_members: Optional[Callable[[], None]] = None,
                      stats: Optional[Dict[str, float]] = None,
                      counter: Optional[list] = None
                      ) -> List[List[Tuple[int, int, float]]]:
    """Q kNN queries over the vector plane. ``specs`` need query_vector /
    filter / filter_key / num_candidates attributes (the batch executor's
    BatchSpec, or the solo rewrite's one-element shim). Returns one
    [(segment_idx, local_doc, raw_score)] winner list (len <= k, score
    order) per member — exactly what the per-segment merge produces.

    Raises PlaneFallback when IVF-routed members disagree on the implied
    probe width (mirrors the per-segment batch rule)."""
    telemetry.mark_plane_served()
    reader = _reader_of(ctxs)
    n_q = len(specs)
    vectors = np.asarray([s.query_vector for s in specs], np.float32)
    winners: List[List[Tuple[int, int, float]]] = [[] for _ in range(n_q)]
    unfiltered = [qi for qi in range(n_q) if specs[qi].filter is None]

    route = None
    if unfiltered:
        route = plane_ann_route(ctxs[0], part, field, k,
                                specs[unfiltered[0]].num_candidates)
    if route is not None:
        index, rows, oversample, nprobe = route
        distinct_nc = {specs[qi].num_candidates for qi in unfiltered}
        if index is not None and len(distinct_nc) > 1:
            widths = {plane_ann_route(ctxs[0], part, field, k, nc)[3]
                      for nc in distinct_nc}
            if len(widths) > 1:
                telemetry.TELEMETRY.count_fallback(
                    telemetry.PLANE_IVF_NPROBE_DISAGREEMENT)
                raise PlaneFallback(
                    "IVF-routed members' num_candidates imply different "
                    "nprobe")
        if index is not None:
            if check_members is not None:
                check_members()
            if counter is not None:
                counter.append(1)
            probed = _probe_plane(index, vectors[unfiltered], k, nprobe,
                                  rows, _live_host(reader), part,
                                  oversample)
            for qi, hits in zip(unfiltered, probed):
                winners[qi] = hits
        exact_idx = [qi for qi in range(n_q)
                     if specs[qi].filter is not None]
    else:
        exact_idx = list(range(n_q))

    if exact_idx:
        if check_members is not None:
            check_members()
        live = part.live_mask(reader.live_masks)
        masks, shared = _filter_mask_rows(ctxs, part, specs, exact_idx)
        if shared and stats is not None:
            stats["knn_shared_mask_segments"] = \
                stats.get("knn_shared_mask_segments", 0) + 1
        k_plane = min(k, part.n_docs_pad)
        # the matmul materializes a [B, n_docs_pad] f32 score plane over
        # the whole shard: charge the request breaker before dispatch
        from elasticsearch_tpu.indices.breaker import BREAKERS
        transient = 8 * part.n_docs_pad * len(exact_idx)
        with BREAKERS.breaker("request").limit_scope(
                transient, "plane_knn"):
            got = None
            if PLANES.quantized and not maps_exact(ctxs[0].mappers, field):
                got = _coarse_attempt(
                    "knn", len(exact_idx),
                    lambda: _quantized_topk(part, vectors[exact_idx],
                                            live, masks, k_plane,
                                            counter=counter))
            if got is None:
                from elasticsearch_tpu.ops.knn import KnnExecutor
                if counter is not None:
                    counter.append(1)
                t_exact = time.monotonic()
                got = KnnExecutor(part).top_k_batch(
                    vectors[exact_idx], live, k_plane, masks)
                _note_exact("knn", len(exact_idx), t_exact)
        s, d = np.asarray(got[0]), np.asarray(got[1])
        for row, qi in enumerate(exact_idx):
            finite = (s[row] > -np.inf) & (d[row] >= 0)
            si, local = part.demux(d[row][finite])
            winners[qi] = [(int(a), int(b), float(sc)) for a, b, sc in
                           zip(si, local, s[row][finite])]
    for qi in range(n_q):
        winners[qi].sort(key=lambda x: -x[2])
        winners[qi] = winners[qi][:k]
    return winners


# ---------------------------------------------------------------------------
# sparse: one gather/scatter over the rank_features plane
# ---------------------------------------------------------------------------

def plane_sparse_topk(ctxs, part, field: str,
                      expansions: List[List[Tuple[str, float]]],
                      want: int,
                      check_members: Optional[Callable[[], None]] = None,
                      counter: Optional[list] = None) -> List[Tuple]:
    """Q resolved expansions scored over the stacked feature blocks in
    ONE device dispatch, exact per-member match counts off the score
    plane. Returns per member (candidates, total, max_score)."""
    from elasticsearch_tpu.ops.sparse import sparse_topk_batch
    telemetry.mark_plane_served()
    reader = _reader_of(ctxs)
    live = part.live_mask(reader.live_masks)
    per = []
    for expansion in expansions:
        idx_parts, w_parts = [], []
        for _pos, ff, block_base in part.refs:
            for name, weight in expansion:
                t_idx = ff.feature_block_idx(name)
                if len(t_idx):
                    idx_parts.append(t_idx + np.int32(block_base))
                    w_parts.append(np.full(len(t_idx), weight,
                                           np.float32))
        if idx_parts:
            per.append((np.concatenate(idx_parts),
                        np.concatenate(w_parts)))
        else:
            per.append((np.zeros(0, np.int32), np.zeros(0, np.float32)))
    qb_pad = next_pow2(max((len(i) for i, _ in per), default=1),
                       minimum=8)
    n_real = len(per)
    q_n = next_pow2(max(n_real, 1), minimum=1)
    idx = np.zeros((q_n, qb_pad), np.int32)
    w = np.zeros((q_n, qb_pad), np.float32)
    for i, (bi, bw) in enumerate(per):
        idx[i, : len(bi)] = bi
        w[i, : len(bw)] = bw
    k_plane = min(max(want, 1), part.n_docs_pad)

    # quantized coarse tier: bf16 coarse gather/scatter + exact f32
    # re-rank with adaptive depth (the text/kNN pattern on the
    # rank_features class); None = exact single-dispatch path below
    if PLANES.quantized:
        got = _coarse_attempt("sparse", n_real, lambda: _coarse_sparse_topk(
            part, idx, w, live, k_plane, n_real, check_members, counter))
        if got is not None:
            s, d, h = got
            return _sparse_demux(part, s, d, h, n_real)

    if check_members is not None:
        check_members()
    if counter is not None:
        counter.append(1)
    telemetry.record_dispatch()
    t_exact = time.monotonic()
    from elasticsearch_tpu.indices.breaker import BREAKERS
    with BREAKERS.breaker("request").limit_scope(
            8 * part.n_docs_pad * q_n, "plane_sparse"):
        s, d, h = sparse_topk_batch(
            part.block_docs, part.block_weights, jnp.asarray(idx),
            jnp.asarray(w), jnp.float32(1.0), jnp.float32(1.0), live,
            part.n_docs_pad, k_plane, "linear", counted=True)
    s, d, h = np.asarray(s), np.asarray(d), np.asarray(h)
    _note_exact("sparse", n_real, t_exact)
    return _sparse_demux(part, s, d, h, n_real)


def _sparse_demux(part, s: np.ndarray, d: np.ndarray, h: np.ndarray,
                  n_real: int) -> List[Tuple]:
    """(candidates, total, max_score) per member from the score/doc/hit
    planes — shared by the exact and coarse-tier sparse paths so the
    result shape cannot diverge."""
    out: List[Tuple] = []
    for qi in range(n_real):
        finite = s[qi] != -np.inf
        si, local = part.demux(d[qi][finite])
        cands = [ShardDoc(int(a), int(b), float(sc), (float(sc),))
                 for a, b, sc in zip(si, local, s[qi][finite])]
        cands.sort(key=lambda c: (-c.score, c.segment_idx, c.doc))
        max_score = max((c.score for c in cands), default=None)
        out.append((cands, int(h[qi]), max_score))
    return out


def _coarse_sparse_topk(part, idx: np.ndarray, w: np.ndarray, live,
                        k_plane: int, n_real: int,
                        check_members: Optional[Callable[[], None]],
                        counter: Optional[list]):
    """Adaptive coarse+re-rank for the sparse plane: returns host
    (scores, docs, hits) arrays shaped like the exact dispatch (hits
    EXACT off the coarse pass), or None when the exact path must serve
    (engage threshold, mirror refused, margin exhausted)."""
    from elasticsearch_tpu.ops.sparse import (
        sparse_coarse_kernel, sparse_rerank_kernel,
    )
    depth0 = _coarse_depth0(k_plane, part.n_docs_pad)
    if part.n_docs_total <= 4 * depth0:
        return None
    mirror = part.quantized_mirror()
    if mirror is None:
        _count_plane_quantized_fallback()
        return None
    idx_dev = jnp.asarray(idx)
    w_dev = jnp.asarray(w)
    q_n = idx.shape[0]
    from elasticsearch_tpu.indices.breaker import BREAKERS
    for kprime, last in _adaptive_depths(k_plane, part.n_docs_pad):
        if check_members is not None:
            check_members()
        if counter is not None:
            counter.extend((1, 1))
        telemetry.record_dispatch(2)
        with BREAKERS.breaker("request").limit_scope(
                8 * part.n_docs_pad * q_n, "plane_coarse_sparse"):
            cs, cand, hits = sparse_coarse_kernel(
                part.block_docs, mirror, idx_dev, w_dev, live,
                part.n_docs_pad, kprime)
            s, d, eps = sparse_rerank_kernel(
                part.block_docs, part.block_weights, idx_dev, w_dev,
                live, cand, cs, part.n_docs_pad, kprime, k_plane)
        cs_h = np.asarray(cs)
        s_h = np.asarray(s)
        eps_h = np.asarray(eps)
        k_last = min(k_plane, s_h.shape[1]) - 1
        if all(_margin_ok(float(s_h[qi, k_last]),
                          float(cs_h[qi, kprime - 1]),
                          float(eps_h[qi]), REL_BF16)
               for qi in range(n_real)):
            PLANES.note_quantized(kprime, n_real)
            return s_h, np.asarray(d), np.asarray(hits)
        if last:
            _count_plane_quantized_fallback()
            return None


# ---------------------------------------------------------------------------
# mesh-sharded plane executors: ONE SPMD program for a whole co-located
# fan-out (ops/device_segment.py MeshPlanePart over a (dp, shard) mesh)
# ---------------------------------------------------------------------------

class MeshFallback(Exception):
    """This fan-out cannot run on the mesh (e.g. an IVF-routed shard, or
    mixed per-shard quantized engagement that only the per-shard path
    can serve byte-identically); the caller runs the per-shard RPC
    fan-out. ``reason`` is the telemetry taxonomy constant the executor
    counts."""

    def __init__(self, msg: str, reason: Optional[str] = None):
        super().__init__(msg)
        self.reason = reason or telemetry.MESH_IVF_ROUTED


def _count_mesh_quantized_fallback() -> None:
    MESH_PLANES.stats["mesh_quantized_fallbacks"] += 1
    telemetry.TELEMETRY.count_fallback(telemetry.MESH_QUANTIZED_FALLBACK)


def _mesh_engages(subs, k: int) -> Optional[bool]:
    """Whether the quantized coarse tier engages for a mesh fan-out:
    True only when EVERY populated slot clears the per-shard engage
    threshold — the same sizing rule the per-shard plane applies — so
    the mesh and the RPC fan-out pick the same tier shard-for-shard.
    None = slots disagree (only the per-shard path can serve each shard
    its own tier; the kNN caller raises MeshFallback for this)."""
    votes = [s.n_docs_total > 4 * _coarse_depth0(k, s.n_docs_pad)
             for s in subs if s is not None]
    if not votes or not any(votes):
        return False
    if all(votes):
        return True
    return None


def _shard_readers(shard_ctxs):
    return [ctxs[0].reader if ctxs else None for ctxs in shard_ctxs]


def _mesh_live(mpart, shard_ctxs) -> np.ndarray:
    """Reader-snapshot live masks per slot, in each sub's plane doc
    layout (padding slots and padding docs stay False) — built per
    dispatch, like the single-shard plane's ``live_mask``, so deletes
    never invalidate the mesh plane itself."""
    out = np.zeros((mpart.n_slots, mpart.n_docs_pad), bool)
    for i, reader in enumerate(_shard_readers(shard_ctxs)):
        if reader is None:
            continue
        off = 0
        for m in reader.live_masks:
            out[i, off: off + len(m)] = np.asarray(m)
            off += len(m)
    return out


def mesh_wand_topk(shard_ctxs, mpart, field: str,
                   clause_lists: List[List[Tuple[str, float]]],
                   want: int, track_limit: int,
                   check_members: Optional[Callable[[], None]] = None,
                   counter: Optional[list] = None
                   ) -> Optional[List[List[Tuple]]]:
    """Q text queries against S co-located shards' postings planes in
    TWO mesh dispatches (phase-A theta, phase-B survivors) plus at most
    one recount — per SHARD semantics identical to plane_wand_topk /
    the per-segment loops, so the coordinator merge over the synthesized
    per-shard results is byte-compatible with the RPC fan-out.

    Returns [shard][member] (candidates, hits, relation, max_score,
    (blocks_total, blocks_scored)).

    DFS-normed fan-outs (coordinator df/avgdl overrides) are served on
    the mesh too, the plane_wand_topk discipline: df overrides flow
    through each segment's planner, the corpus-wide avgdl replaces the
    baked per-block values in the flat dispatch argument — so a DFS
    query costs the same 2-3 mesh dispatches as a plain one instead of
    a per-shard RPC fan-out."""
    from elasticsearch_tpu.ops.bm25 import flatten_plans, qb_bucket
    from elasticsearch_tpu.parallel.mesh import mesh_bm25_flat
    from elasticsearch_tpu.search.execute import _bm25_planner

    counts_on = track_limit > 0
    n_q = len(clause_lists)
    n_sh = mpart.n_shards
    # the flat gather stacks split over dp (each row scores its own
    # contiguous slice of the micro-batch), so the padded count must
    # fill the rows evenly — the kNN query-stack rule
    dp = max(1, int(mpart.mesh.shape["dp"]))
    n_q_pad = next_pow2(max(n_q, 1), minimum=1)
    n_q_pad = -(-n_q_pad // dp) * dp
    n_q_row = n_q_pad // dp
    empty = ([], 0, "eq", None, (0, 0))
    empty_plan = QueryPlan([], [], [], [])

    avgdl_override = None
    prepped: List[Optional[Dict]] = []
    for si in range(n_sh):
        sub = mpart.subs[si]
        if sub is None:
            prepped.append(None)
            continue
        ctxs = shard_ctxs[si]
        per_seg = []        # (plans[n_q], block_base)
        seen: List[Dict[str, float]] = [{} for _ in range(n_q)]
        has = [False] * n_q
        for pos, _pf, block_base, avgdl in sub.refs:
            ctx = ctxs[pos]
            override = ctx.avgdl_for(field)
            if override is not None:
                # DFS-normed: one corpus-wide value for every segment
                # of every member shard (it is per-request per-field)
                avgdl_override = float(override)
                avgdl = avgdl_override
            analyzer = ctx.search_analyzer(field)
            ex = _bm25_planner(ctx, field)
            if ex is None:
                continue
            df_map = ctx.df_for(field) or {}
            member_terms: List[List[Tuple[str, float]]] = []
            any_terms = False
            for qi, clauses in enumerate(clause_lists):
                terms: List[Tuple[str, float]] = []
                for text, boost in clauses:
                    terms.extend((t, boost)
                                 for t in analyzer.terms(text))
                member_terms.append(terms)
                if terms:
                    any_terms = True
                    has[qi] = True
                    for t, _b in terms:
                        if t not in seen[qi]:
                            seen[qi][t] = float(df_map.get(t, 0))
            if not any_terms:
                continue
            plans = ex.build_plans(member_terms,
                                   df_override=df_map or None,
                                   avgdl=avgdl)
            per_seg.append((plans, block_base))
        prepped.append({"per_seg": per_seg, "seen": seen, "has": has}
                       if per_seg else None)

    if all(p is None for p in prepped):
        return [[empty] * n_q for _ in range(n_sh)]

    exact_mode = np.zeros((n_sh, n_q), bool)
    for si, p in enumerate(prepped):
        if p is None:
            continue
        for qi in range(n_q):
            upper = int(sum(p["seen"][qi].values()))
            exact_mode[si, qi] = counts_on and upper <= track_limit

    k_mesh = min(max(want, 1), mpart.n_docs_pad)
    live_host = _mesh_live(mpart, shard_ctxs)

    def _dispatch(rows_by_shard, k):
        if check_members is not None:
            check_members()
        # one flat-bucket for every (slot, dp row) group: per-row flats
        # keep each row's qids local (0..n_q_row-1), so the kernel's
        # scatter per row is exactly the single-shard flat kernel's
        fb = qb_bucket(max(
            [sum(p.n_blocks
                 for p in rows[r * n_q_row: (r + 1) * n_q_row])
             for rows in rows_by_shard if rows for r in range(dp)]
            + [1]))
        idx = np.zeros((mpart.n_slots, dp, fb), np.int32)
        w = np.zeros((mpart.n_slots, dp, fb), np.float32)
        qid = np.zeros((mpart.n_slots, dp, fb), np.int32)
        favg = np.ones((mpart.n_slots, dp, fb), np.float32)
        for si, rows in enumerate(rows_by_shard):
            if not rows:
                continue
            for r in range(dp):
                i_s, w_s, q_s = flatten_plans(
                    rows[r * n_q_row: (r + 1) * n_q_row], fb)
                idx[si, r], w[si, r], qid[si, r] = i_s, w_s, q_s
                favg[si, r] = avgdl_override if avgdl_override \
                    is not None else mpart.subs[si].block_avgdl[i_s]
        fn = mesh_bm25_flat(mpart.mesh, mpart.n_docs_pad, n_q_row, k,
                            mpart.n_segs_max, DEFAULT_K1, DEFAULT_B)
        from elasticsearch_tpu.indices.breaker import BREAKERS
        transient = 8 * mpart.n_docs_pad * n_q_pad * mpart.n_slots
        with BREAKERS.breaker("request").limit_scope(
                transient, "mesh_wand_topk"):
            if counter is not None:
                counter.append(1)
            telemetry.record_dispatch()
            s, d, h = fn(mpart.block_docs, mpart.block_tfs,
                         mpart.doc_lens, jnp.asarray(idx),
                         jnp.asarray(w), jnp.asarray(qid),
                         jnp.asarray(favg), jnp.asarray(live_host),
                         mpart.seg_ids)
        # [S, dp, n_q_row, ...] -> [S, n_q_pad, ...]: contiguous row
        # assignment makes the flatten restore micro-batch order
        s = np.asarray(s).reshape(mpart.n_slots, n_q_pad, -1)
        d = np.asarray(d).reshape(mpart.n_slots, n_q_pad, -1)
        h = np.asarray(h).reshape(mpart.n_slots, n_q_pad, -1)
        return s, d, h

    def _rows(select):
        """[slot][n_q_pad] plan rows; ``select(si, qi, plans)`` -> plan
        for that (shard, member, segment) or empty_plan."""
        out = []
        for si in range(mpart.n_slots):
            p = prepped[si] if si < n_sh else None
            if p is None:
                out.append(None)
                continue
            rows = []
            for qi in range(n_q):
                segs = [select(si, qi, plans[qi])
                        for plans, _bb in p["per_seg"]]
                rows.append(QueryPlan.concat(
                    segs,
                    idx_offsets=[bb for _pl, bb in p["per_seg"]]))
            rows.extend([empty_plan] * (n_q_pad - n_q))
            out.append(rows)
        return out

    def _try_coarse() -> Optional[List[List[Tuple]]]:
        """Quantized two-tier mesh text path: one bf16 coarse mesh
        dispatch over the full plans + one exact f32 re-rank mesh
        dispatch, adaptive depth deepening GLOBALLY (any (shard, member)
        with a tight margin re-dispatches the whole program). Per-slot
        bodies are the single-shard coarse/re-rank bodies, so re-ranked
        scores are bit-compatible with the per-shard quantized path —
        and counts come exact off the coarse pass. None = the exact mesh
        phases below serve (typed when it is a fallback)."""
        from elasticsearch_tpu.ops.bm25 import (
            flatten_plans, qb_bucket, range_bucket, rerank_ranges,
        )
        from elasticsearch_tpu.parallel.mesh import (
            mesh_bm25_coarse, mesh_bm25_rerank,
        )
        if _mesh_engages(mpart.subs, k_mesh) is not True:
            return None
        mirror = mpart.quantized_mirror()
        if mirror is None:
            _count_mesh_quantized_fallback()
            return None
        tf16, dl16 = mirror
        rows_full = _rows(lambda si, qi, p: p)
        fb = qb_bucket(max(
            [sum(p.n_blocks for p in rows)
             for rows in rows_full if rows] + [1]))
        idx = np.zeros((mpart.n_slots, fb), np.int32)
        w = np.zeros((mpart.n_slots, fb), np.float32)
        qid = np.zeros((mpart.n_slots, fb), np.int32)
        favg = np.ones((mpart.n_slots, fb), np.float32)
        for si, rows in enumerate(rows_full):
            if not rows:
                continue
            i_s, w_s, q_s = flatten_plans(rows, fb)
            idx[si], w[si], qid[si] = i_s, w_s, q_s
            favg[si] = avgdl_override if avgdl_override is not None \
                else mpart.subs[si].block_avgdl[i_s]
        # the re-rank's term ranges, [S, n_q_pad, R] with one R for all
        # slots (the coarse gather's slot-major layout, per range)
        r_pad = max(range_bucket(rows) for rows in rows_full if rows)
        r_host = [np.zeros((mpart.n_slots, n_q_pad, r_pad), dt)
                  for dt in (np.int32, np.int32, np.float32, np.float32)]
        for si, rows in enumerate(rows_full):
            if not rows:
                continue
            b_avg = mpart.subs[si].block_avgdl
            if avgdl_override is not None:
                b_avg = np.full_like(b_avg, avgdl_override)
            for dst, got in zip(r_host, rerank_ranges(rows, n_q_pad,
                                                      b_avg, r_pad)):
                dst[si] = got
        ranges_dev = [jnp.asarray(a) for a in r_host]
        idx_dev, w_dev = jnp.asarray(idx), jnp.asarray(w)
        qid_dev, favg_dev = jnp.asarray(qid), jnp.asarray(favg)
        live_dev = jnp.asarray(live_host)
        blocks_full = np.zeros((n_sh, n_q), np.int64)
        for si, rows in enumerate(rows_full):
            if si < n_sh and rows:
                for qi in range(n_q):
                    blocks_full[si, qi] = rows[qi].n_blocks

        from elasticsearch_tpu.indices.breaker import BREAKERS
        for kprime, last in _adaptive_depths(k_mesh, mpart.n_docs_pad):
            if check_members is not None:
                check_members()
            c_fn = mesh_bm25_coarse(mpart.mesh, mpart.n_docs_pad,
                                    n_q_pad, kprime, mpart.n_segs_max,
                                    DEFAULT_K1, DEFAULT_B)
            r_fn = mesh_bm25_rerank(mpart.mesh, k_mesh, DEFAULT_K1,
                                    DEFAULT_B)
            transient = 8 * mpart.n_docs_pad * n_q_pad * mpart.n_slots
            with BREAKERS.breaker("request").limit_scope(
                    transient, "mesh_coarse_wand"):
                if counter is not None:
                    counter.extend((1, 1))
                telemetry.record_dispatch(2)
                cs, cand, hits = c_fn(mpart.block_docs, tf16, dl16,
                                      idx_dev, w_dev, qid_dev, favg_dev,
                                      live_dev, mpart.seg_ids)
                s, d, eps = r_fn(mpart.block_docs, mpart.block_tfs,
                                 *ranges_dev, mpart.doc_lens, live_dev,
                                 cand, cs)
            cs_h, s_h = np.asarray(cs), np.asarray(s)
            eps_h = np.asarray(eps)
            k_last = min(k_mesh, s_h.shape[2]) - 1
            ok = all(
                _margin_ok(float(s_h[si, qi, k_last]),
                           float(cs_h[si, qi, kprime - 1]),
                           float(eps_h[si, qi]), REL_BF16)
                for si in range(n_sh) if prepped[si] is not None
                for qi in range(n_q) if prepped[si]["has"][qi])
            if ok:
                break
            if last:
                _count_mesh_quantized_fallback()
                return None

        hits_h = np.asarray(hits)
        d_h = np.asarray(d)
        # members with terms in ANY slot — the same members the
        # per-shard path would have counted as coarse-tier-served
        n_served = sum(
            1 for qi in range(n_q)
            if any(p is not None and p["has"][qi] for p in prepped))
        MESH_PLANES.stats["mesh_quantized_queries"] += n_served
        PLANES.note_quantized(kprime, n_served, mesh=True)
        out: List[List[Tuple]] = []
        for si in range(n_sh):
            p = prepped[si]
            if p is None:
                out.append([empty] * n_q)
                continue
            sub = mpart.subs[si]
            n_segs_here = len(sub.segments)
            row_out: List[Tuple] = []
            for qi in range(n_q):
                if not p["has"][qi]:
                    row_out.append(empty)
                    continue
                s_row, d_row = s_h[si, qi], d_h[si, qi]
                finite = s_row != -np.inf
                seg, local = sub.demux(d_row[finite])
                cands = [ShardDoc(int(a), int(b), float(sc),
                                  (float(sc),))
                         for a, b, sc in zip(seg, local, s_row[finite])]
                cands.sort(key=lambda c: (-c.score, c.segment_idx,
                                          c.doc))
                max_score = max((c.score for c in cands), default=None)
                prune = (int(blocks_full[si, qi]),
                         int(blocks_full[si, qi]))
                h_row = hits_h[si, qi][:n_segs_here]
                if not counts_on:
                    total = int(np.minimum(h_row, want).sum())
                    row_out.append((cands, total, "gte", max_score,
                                    prune))
                    continue
                hits_seen = int(h_row.sum())
                if hits_seen >= track_limit:
                    row_out.append((cands, track_limit, "gte",
                                    max_score, prune))
                else:
                    row_out.append((cands, hits_seen, "eq", max_score,
                                    prune))
            out.append(row_out)
        return out

    if PLANES.quantized:
        # the measured-latency engage rule, per MESH class: the mesh
        # coarse tier pays 2 dispatches over n_slots stacks, so it gets
        # its own EWMAs rather than inheriting the single-shard ones
        got_coarse = _coarse_attempt("mesh_bm25", n_q, _try_coarse)
        if got_coarse is not None:
            return got_coarse
    t_exact = time.monotonic()

    # phase A — one mesh dispatch: exact-mode (shard, member) pairs score
    # all their blocks (their counts are final), pruned pairs their
    # per-segment P1_BUCKET highest-upper-bound blocks
    rows_a = _rows(lambda si, qi, p:
                   p if exact_mode[si, qi] else p.top_by_ub(P1_BUCKET))
    s_a, d_a, h_a = _dispatch(rows_a, k_mesh)

    theta = np.full((n_sh, n_q), -np.inf)
    for si, p in enumerate(prepped):
        if p is None:
            continue
        for qi in range(n_q):
            if exact_mode[si, qi]:
                continue
            finite = s_a[si, qi][np.isfinite(s_a[si, qi])]
            if len(finite) >= want:
                theta[si, qi] = float(np.sort(finite)[-want])

    # phase B — one mesh dispatch: per-(shard, member) WAND survivors
    blocks_total = np.zeros((n_sh, n_q), np.int64)
    blocks_scored = np.zeros((n_sh, n_q), np.int64)
    hits_exact = np.ones((n_sh, n_q), bool)

    def _survivors(si, qi, p):
        if exact_mode[si, qi]:
            blocks_total[si, qi] += p.n_blocks
            blocks_scored[si, qi] += p.n_blocks
            return empty_plan
        surv = p.survivors(float(theta[si, qi]))
        p1_cost = min(p.n_blocks, P1_BUCKET)
        blocks_total[si, qi] += p.n_blocks
        blocks_scored[si, qi] += min(surv.n_blocks + p1_cost, p.n_blocks)
        if surv.n_blocks < p.n_blocks:
            hits_exact[si, qi] = False
        return surv

    rows_b = _rows(_survivors)
    need_b = any(
        not exact_mode[si, qi]
        for si in range(n_sh) if prepped[si] is not None
        for qi in range(n_q))
    if need_b:
        s_b, d_b, h_b = _dispatch(rows_b, k_mesh)
    else:
        s_b = d_b = h_b = None

    out: List[List[Tuple]] = []
    for si in range(n_sh):
        p = prepped[si]
        row_out: List[Tuple] = []
        if p is None:
            out.append([empty] * n_q)
            continue
        sub = mpart.subs[si]
        for qi in range(n_q):
            if not p["has"][qi]:
                row_out.append(empty)
                continue
            if exact_mode[si, qi]:
                s_row, d_row = s_a[si, qi], d_a[si, qi]
                h_row = h_a[si, qi]
            else:
                s_row, d_row = s_b[si, qi], d_b[si, qi]
                h_row = h_b[si, qi]
            finite = s_row != -np.inf
            seg, local = sub.demux(d_row[finite])
            cands = [ShardDoc(int(a), int(b), float(sc), (float(sc),))
                     for a, b, sc in zip(seg, local, s_row[finite])]
            cands.sort(key=lambda c: (-c.score, c.segment_idx, c.doc))
            max_score = max((c.score for c in cands), default=None)
            prune = (int(blocks_total[si, qi]),
                     int(blocks_scored[si, qi]))
            if not counts_on:
                total = int(np.minimum(h_row, want).sum())
                row_out.append((cands, total, "gte", max_score, prune))
                continue
            hits_seen = int(h_row.sum())
            if hits_seen >= track_limit:
                row_out.append((cands, track_limit, "gte", max_score,
                                prune))
            elif hits_exact[si, qi] or exact_mode[si, qi]:
                row_out.append((cands, hits_seen, "eq", max_score,
                                prune))
            else:
                row_out.append((cands, None, None, max_score, prune))
        out.append(row_out)

    # (shard, member) pairs whose pruned counts might hide hits: one
    # exact unpruned counted mesh pass (k=1; scores already final)
    recount = {(si, qi)
               for si in range(n_sh) for qi in range(n_q)
               if counts_on and prepped[si] is not None
               and out[si][qi][1] is None}
    if recount:
        rows_r = _rows(lambda si, qi, p:
                       p if (si, qi) in recount else empty_plan)
        _s, _d, h_r = _dispatch(rows_r, 1)
        for si, qi in recount:
            cands, _, _, max_score, prune = out[si][qi]
            exact_hits = int(h_r[si, qi].sum())
            # >= : relation at count == track_limit is "gte" on every
            # path (the plane recount's boundary rule)
            if exact_hits >= track_limit:
                out[si][qi] = (cands, track_limit, "gte", max_score,
                               prune)
            else:
                out[si][qi] = (cands, exact_hits, "eq", max_score,
                               prune)
    _note_exact("mesh_bm25", n_q, t_exact)
    return out


def mesh_knn_winners(shard_ctxs, mpart, field: str, specs, k: int,
                     check_members: Optional[Callable[[], None]] = None,
                     counter: Optional[list] = None
                     ) -> List[List[List[Tuple[int, int, float]]]]:
    """Q kNN queries against S co-located shards' vector planes in ONE
    mesh dispatch: the query stack rides the dp axis, the corpus the
    shard axis, and each slot's row reproduces that shard's exact plane
    matmul (plane_knn_winners' exact path). When EVERY populated slot
    clears the quantized engage threshold, the int8 mirrors stacked per
    mesh slot serve the coarse pass and the exact re-rank restores
    golden scores (each slot running the per-shard two-tier arithmetic,
    adaptive depth deepening globally) — the mesh no longer serves
    exact-only.

    Returns [shard][member] winner lists [(segment_idx, local_doc,
    raw_score)]. Raises MeshFallback for IVF-routed shards (mapping
    opt-in or ANN-sized corpora) and for MIXED per-shard quantized
    engagement (only the per-shard fan-out serves each shard its own
    tier byte-identically) — those keep the per-shard fan-out."""
    from elasticsearch_tpu.parallel.mesh import mesh_knn_topk
    from elasticsearch_tpu.search.execute import (
        ANN_DEFAULT_MIN_DOCS, execute as execute_query,
    )
    n_q = len(specs)
    n_sh = mpart.n_shards

    ctx0 = next((ctxs[0] for ctxs in shard_ctxs if ctxs), None)
    opts = {}
    if ctx0 is not None:
        mapper = ctx0.mappers.mapper(field)
        opts = getattr(mapper, "index_options", None) or {}
        if opts.get("type") == "ivf":
            raise MeshFallback(
                f"[{field}] is IVF-mapped: the per-shard probe serves")
    # a "flat" field is exact at any size: no IVF route, no int8 tier
    exact = opts.get("type") == "flat"
    for sub in () if exact else mpart.subs:
        if sub is None:
            continue
        sizes = [s.n_docs for s in sub.segments
                 if s.vectors.get(field) is not None]
        if sizes and min(sizes) >= ANN_DEFAULT_MIN_DOCS:
            raise MeshFallback(
                "ANN-sized shard would take the per-segment IVF route")

    if check_members is not None:
        check_members()
    vectors = np.asarray([s.query_vector for s in specs], np.float32)
    dp = max(1, int(mpart.mesh.shape["dp"]))
    # the single-shard kNN pad's row floor: a slot's [B, D] x [D, N] dot
    # then has the fan-out's shape, so mesh and RPC scores agree to the bit
    from elasticsearch_tpu.ops.knn import MIN_QUERY_ROWS
    n_q_pad = next_pow2(max(n_q, 1), minimum=MIN_QUERY_ROWS)
    n_q_pad = -(-n_q_pad // dp) * dp
    q_host = np.zeros((n_q_pad, vectors.shape[1]), np.float32)
    q_host[:n_q] = vectors

    live_host = _mesh_live(mpart, shard_ctxs)
    # distinct filters resolve to masks once per (filter, shard) — the
    # batched executor's sharing rule, stacked into mesh slot space
    fkeys = {s.filter_key for s in specs}
    masks_host = None
    if fkeys != {None}:
        by_key: Dict[Optional[str], np.ndarray] = {}
        for fk in fkeys:
            if fk is None:
                continue
            spec = next(s for s in specs if s.filter_key == fk)
            rows = np.zeros((mpart.n_slots, mpart.n_docs_pad), bool)
            for si in range(n_sh):
                sub = mpart.subs[si]
                if sub is None:
                    continue
                for pos, ctx in enumerate(shard_ctxs[si]):
                    _, fmask = execute_query(spec.filter, ctx)
                    base = int(sub.doc_base[pos])
                    n = ctx.segment.n_docs
                    rows[si, base: base + n] = np.asarray(fmask)[:n]
            by_key[fk] = rows
        if len(fkeys) == 1:
            # every member carries the SAME filter: fold it into the
            # allowed mask (one unmasked dispatch)
            live_host = live_host & by_key[next(iter(fkeys))]
        else:
            masks_host = np.ones(
                (mpart.n_slots, n_q_pad, mpart.n_docs_pad), bool)
            for qi, spec in enumerate(specs):
                if spec.filter_key is not None:
                    masks_host[:, qi, :] = by_key[spec.filter_key]

    k_mesh = min(max(k, 1), mpart.n_docs_pad)
    allowed = jnp.logical_and(jnp.asarray(live_host), mpart.exists)
    q_dev = jnp.asarray(q_host)
    masks_dev = jnp.asarray(masks_host) if masks_host is not None \
        else None
    from elasticsearch_tpu.indices.breaker import BREAKERS
    transient = 8 * mpart.n_docs_pad * n_q_pad * mpart.n_slots

    def _try_quantized():
        """int8 coarse + exact re-rank over the stacked mirrors, the
        adaptive-depth loop deepening globally. None = exact mesh
        kernel serves (mirror refused / margin exhausted, typed)."""
        from elasticsearch_tpu.parallel.mesh import (
            mesh_knn_coarse, mesh_knn_rerank,
        )
        mirror = mpart.quantized_mirror()
        if mirror is None:
            _count_mesh_quantized_fallback()
            return None
        q8, scales = mirror
        for kprime, last in _adaptive_depths(k_mesh, mpart.n_docs_pad):
            if check_members is not None:
                check_members()
            c_fn = mesh_knn_coarse(mpart.mesh, kprime, mpart.similarity,
                                   masked=masks_dev is not None)
            r_fn = mesh_knn_rerank(mpart.mesh, k_mesh, mpart.similarity,
                                   masked=masks_dev is not None)
            with BREAKERS.breaker("request").limit_scope(
                    transient, "mesh_coarse_knn"):
                if counter is not None:
                    counter.extend((1, 1))
                telemetry.record_dispatch(2)
                if masks_dev is not None:
                    cs, cand = c_fn(q8, scales, mpart.norms, allowed,
                                    q_dev, masks_dev)
                    s_q, d_q, eps = r_fn(mpart.matrix, mpart.norms,
                                         allowed, q_dev, cand, cs,
                                         masks_dev)
                else:
                    cs, cand = c_fn(q8, scales, mpart.norms, allowed,
                                    q_dev)
                    s_q, d_q, eps = r_fn(mpart.matrix, mpart.norms,
                                         allowed, q_dev, cand, cs)
            cs_h, s_h = np.asarray(cs), np.asarray(s_q)
            eps_h = np.asarray(eps)
            k_last = min(k_mesh, s_h.shape[2]) - 1
            if all(_margin_ok(float(s_h[si, qi, k_last]),
                              float(cs_h[si, qi, kprime - 1]),
                              float(eps_h[si, qi]), REL_INT8)
                   for si in range(n_sh)
                   if mpart.subs[si] is not None
                   for qi in range(n_q)):
                MESH_PLANES.stats["mesh_quantized_queries"] += n_q
                PLANES.note_quantized(kprime, n_q, mesh=True)
                return s_h, np.asarray(d_q)
            if last:
                _count_mesh_quantized_fallback()
                return None

    got_q = None
    if PLANES.quantized and not exact:
        engages = _mesh_engages(mpart.subs, k_mesh)
        if engages is None:
            # counted on the stats surface here (the executor counts the
            # telemetry reason when it converts this to a mesh miss)
            MESH_PLANES.stats["mesh_quantized_fallbacks"] += 1
            raise MeshFallback(
                "per-shard quantized engagement is mixed: the per-shard "
                "fan-out serves each shard its own tier",
                reason=telemetry.MESH_QUANTIZED_FALLBACK)
        if engages:
            got_q = _coarse_attempt("mesh_knn", n_q, _try_quantized)
    if got_q is not None:
        s, d = got_q
    else:
        t_exact = time.monotonic()
        fn = mesh_knn_topk(mpart.mesh, k_mesh, mpart.similarity,
                           masked=masks_host is not None)
        with BREAKERS.breaker("request").limit_scope(transient,
                                                     "mesh_knn"):
            if counter is not None:
                counter.append(1)
            telemetry.record_dispatch()
            if masks_dev is not None:
                s, d = fn(mpart.matrix, mpart.norms, allowed, q_dev,
                          masks_dev)
            else:
                s, d = fn(mpart.matrix, mpart.norms, allowed, q_dev)
        s, d = np.asarray(s), np.asarray(d)
        _note_exact("mesh_knn", n_q, t_exact)

    winners: List[List[List[Tuple[int, int, float]]]] = []
    for si in range(n_sh):
        sub = mpart.subs[si]
        row: List[List[Tuple[int, int, float]]] = []
        for qi in range(n_q):
            if sub is None:
                row.append([])
                continue
            finite = s[si, qi] > -np.inf
            seg, local = sub.demux(d[si, qi][finite])
            row.append([(int(a), int(b), float(sc)) for a, b, sc in
                        zip(seg, local, s[si, qi][finite])])
        winners.append(row)
    return winners


def mesh_sparse_topk(shard_ctxs, mpart, field: str,
                     expansions: List[List[Tuple[str, float]]],
                     want: int,
                     check_members: Optional[Callable[[], None]] = None,
                     counter: Optional[list] = None) -> List[List[Tuple]]:
    """Q resolved expansions against S co-located shards' rank_features
    planes in ONE mesh dispatch, exact per-shard match counts off the
    score plane. Returns [shard][member] (candidates, total,
    max_score) — plane_sparse_topk's shape per shard."""
    from elasticsearch_tpu.parallel.mesh import (
        mesh_sparse_topk as _mesh_sparse_kernel,
    )
    n_q = len(expansions)
    n_sh = mpart.n_shards
    # the exact kernel splits the query stack over dp rows (contiguous
    # slices), so the padded count must fill the rows evenly
    dp = max(1, int(mpart.mesh.shape["dp"]))
    n_q_pad = next_pow2(max(n_q, 1), minimum=1)
    n_q_pad = -(-n_q_pad // dp) * dp
    n_q_row = n_q_pad // dp

    per_shard: List[Optional[List[Tuple[np.ndarray, np.ndarray]]]] = []
    qb_max = 1
    for si in range(n_sh):
        sub = mpart.subs[si]
        if sub is None:
            per_shard.append(None)
            continue
        per = []
        for expansion in expansions:
            idx_parts, w_parts = [], []
            for _pos, ff, block_base in sub.refs:
                for name, weight in expansion:
                    t_idx = ff.feature_block_idx(name)
                    if len(t_idx):
                        idx_parts.append(t_idx + np.int32(block_base))
                        w_parts.append(np.full(len(t_idx), weight,
                                               np.float32))
            if idx_parts:
                per.append((np.concatenate(idx_parts),
                            np.concatenate(w_parts)))
                qb_max = max(qb_max, len(per[-1][0]))
            else:
                per.append((np.zeros(0, np.int32),
                            np.zeros(0, np.float32)))
        per_shard.append(per)

    qb_pad = next_pow2(qb_max, minimum=8)
    idx = np.zeros((mpart.n_slots, n_q_pad, qb_pad), np.int32)
    w = np.zeros((mpart.n_slots, n_q_pad, qb_pad), np.float32)
    for si, per in enumerate(per_shard):
        if per is None:
            continue
        for qi, (bi, bw) in enumerate(per):
            idx[si, qi, : len(bi)] = bi
            w[si, qi, : len(bw)] = bw

    if check_members is not None:
        check_members()
    live_host = _mesh_live(mpart, shard_ctxs)
    k_mesh = min(max(want, 1), mpart.n_docs_pad)
    idx_dev, w_dev = jnp.asarray(idx), jnp.asarray(w)
    live_dev = jnp.asarray(live_host)
    from elasticsearch_tpu.indices.breaker import BREAKERS
    transient = 8 * mpart.n_docs_pad * n_q_pad * mpart.n_slots

    def _try_quantized():
        """bf16 coarse + exact f32 re-rank over the stacked weight
        mirrors, adaptive depth deepening globally; counts come exact
        off the coarse pass. None = exact mesh kernel serves (typed
        when it is a fallback)."""
        from elasticsearch_tpu.parallel.mesh import (
            mesh_sparse_coarse, mesh_sparse_rerank,
        )
        if _mesh_engages(mpart.subs, k_mesh) is not True:
            return None
        mirror = mpart.quantized_mirror()
        if mirror is None:
            _count_mesh_quantized_fallback()
            return None
        (w16,) = mirror
        for kprime, last in _adaptive_depths(k_mesh, mpart.n_docs_pad):
            if check_members is not None:
                check_members()
            c_fn = mesh_sparse_coarse(mpart.mesh, mpart.n_docs_pad,
                                      kprime)
            r_fn = mesh_sparse_rerank(mpart.mesh, mpart.n_docs_pad,
                                      kprime, k_mesh)
            with BREAKERS.breaker("request").limit_scope(
                    transient, "mesh_coarse_sparse"):
                if counter is not None:
                    counter.extend((1, 1))
                telemetry.record_dispatch(2)
                cs, cand, hits = c_fn(mpart.block_docs, w16, idx_dev,
                                      w_dev, live_dev)
                s_q, d_q, eps = r_fn(mpart.block_docs,
                                     mpart.block_weights, idx_dev,
                                     w_dev, live_dev, cand, cs)
            cs_h, s_h = np.asarray(cs), np.asarray(s_q)
            eps_h = np.asarray(eps)
            k_last = min(k_mesh, s_h.shape[2]) - 1
            if all(_margin_ok(float(s_h[si, qi, k_last]),
                              float(cs_h[si, qi, kprime - 1]),
                              float(eps_h[si, qi]), REL_BF16)
                   for si in range(n_sh)
                   if per_shard[si] is not None
                   for qi in range(n_q)):
                MESH_PLANES.stats["mesh_quantized_queries"] += n_q
                PLANES.note_quantized(kprime, n_q, mesh=True)
                return s_h, np.asarray(d_q), np.asarray(hits)
            if last:
                _count_mesh_quantized_fallback()
                return None

    got_q = _coarse_attempt("mesh_sparse", n_q, _try_quantized) \
        if PLANES.quantized else None
    if got_q is not None:
        s, d, h = got_q
    else:
        t_exact = time.monotonic()
        fn = _mesh_sparse_kernel(mpart.mesh, mpart.n_docs_pad, k_mesh)
        with BREAKERS.breaker("request").limit_scope(
                transient, "mesh_sparse"):
            if counter is not None:
                counter.append(1)
            telemetry.record_dispatch()
            # the dp-split exact kernel: [S, dp, n_q_row, QB] rows in,
            # [S, dp, n_q_row, ...] out, restitched to batch order
            s, d, h = fn(mpart.block_docs, mpart.block_weights,
                         jnp.asarray(idx.reshape(
                             mpart.n_slots, dp, n_q_row, -1)),
                         jnp.asarray(w.reshape(
                             mpart.n_slots, dp, n_q_row, -1)),
                         live_dev)
        s = np.asarray(s).reshape(mpart.n_slots, n_q_pad, -1)
        d = np.asarray(d).reshape(mpart.n_slots, n_q_pad, -1)
        h = np.asarray(h).reshape(mpart.n_slots, n_q_pad)
        _note_exact("mesh_sparse", n_q, t_exact)

    out: List[List[Tuple]] = []
    for si in range(n_sh):
        sub = mpart.subs[si]
        row: List[Tuple] = []
        for qi in range(n_q):
            if sub is None:
                row.append(([], 0, None))
                continue
            finite = s[si, qi] != -np.inf
            seg, local = sub.demux(d[si, qi][finite])
            cands = [ShardDoc(int(a), int(b), float(sc), (float(sc),))
                     for a, b, sc in zip(seg, local, s[si, qi][finite])]
            cands.sort(key=lambda c: (-c.score, c.segment_idx, c.doc))
            max_score = max((c.score for c in cands), default=None)
            row.append((cands, int(h[si, qi]), max_score))
        out.append(row)
    return out
