"""The exact BM25 re-rank looks candidates up in term ranges.

``bm25_rerank_body`` re-scores the coarse tier's candidates by bisecting
each query term's doc-sorted posting blocks instead of re-reading every
posting. Its (score, doc) lists and ``eps`` must be bit-equal to a
direct re-score of the same candidates off ``bm25_flat_body``'s dense
f32 plane — over 1 and 4 segments, padded last blocks, candidates that
match nothing, repeated terms, a DFS avgdl override, padded queries and
padded ranges — and ``rerank_lookups`` counts the lookups issued.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.index import InternalEngine
from elasticsearch_tpu.index.segment import (
    BLOCK, next_pow2, postings_from_token_matrix,
)
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.ops.bm25 import (
    DEFAULT_B, DEFAULT_K1, QueryPlan, _bm25_rerank_kernel, bm25_flat_body,
    build_query_plan, flatten_plans, idf, qb_bucket, range_bucket,
    rerank_ranges,
)
from elasticsearch_tpu.ops.device_segment import PLANES
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.phase import parse_sort, query_shard

VOCAB = 40
SEG_DOCS = 700
K = 10


def _plane(n_segs: int, seed: int):
    """A packed plane of ``n_segs`` segments built from zipf token
    matrices (frequent terms span several blocks, most last blocks pad
    with -1; two terms are missing from odd segments and hold one all
    -1 block there). Returns the host plane
    arrays and, per segment, (PostingsField, block base, avgdl)."""
    rng = np.random.default_rng(seed)
    docs, tfs, lens, refs = [], [], [], []
    nb = 0
    for s in range(n_segs):
        toks = np.minimum(rng.zipf(1.3, (SEG_DOCS, 24)) - 1, VOCAB - 1)
        toks[rng.random(toks.shape) < 0.3] = -1
        toks[:, 0] = rng.integers(0, 30, SEG_DOCS)    # no empty doc
        # w36 and w37 only in even segments: elsewhere their one block
        # holds no posting
        if s % 2:
            toks[np.isin(toks, [36, 37])] = -1
        pf = postings_from_token_matrix(
            toks, [f"w{i}" for i in range(VOCAB)])
        base = s * SEG_DOCS
        docs.append(np.where(pf.block_docs >= 0, pf.block_docs + base, -1))
        tfs.append(pf.block_tfs)
        lens.append(pf.doc_lens)
        refs.append((pf, nb, float(pf.sum_doc_len / SEG_DOCS)))
        nb += pf.block_docs.shape[0]
    n_docs_pad = next_pow2(n_segs * SEG_DOCS, minimum=BLOCK)
    nb_pad = next_pow2(nb)
    bd = np.full((nb_pad, BLOCK), -1, np.int32)
    bt = np.zeros((nb_pad, BLOCK), np.float32)
    bd[:nb], bt[:nb] = np.concatenate(docs), np.concatenate(tfs)
    dl = np.zeros(n_docs_pad, np.float32)
    dl[: n_segs * SEG_DOCS] = np.concatenate(lens)
    block_avg = np.ones(nb_pad, np.float32)
    for pf, base, avg in refs:
        block_avg[base: base + pf.block_docs.shape[0]] = avg
    return bd, bt, dl, block_avg, refs


def _rows(refs, queries, n_docs, avgdl_override=None):
    """One plane row plan per query (term list, repeats kept): each
    segment's plan, block bases added — the plane executor's layout."""
    rows = []
    for terms in queries:
        seg_plans = []
        for pf, _base, avg in refs:
            tw = [(t, idf(n_docs, int(pf.doc_freq[pf.terms[t]])))
                  for t in terms]
            impact = pf.block_max_impact(
                DEFAULT_K1, DEFAULT_B,
                avg if avgdl_override is None else avgdl_override)
            seg_plans.append(build_query_plan(tw, pf.term_blocks, impact))
        rows.append(QueryPlan.concat(seg_plans,
                                     idx_offsets=[b for _p, b, _a in refs]))
    return rows


def _dense(bd, bt, dl, live, rows, block_avg, n_q_pad):
    """The masked [n_q_pad, n_docs_pad] f32 plane of ``bm25_flat_body``."""
    fb = qb_bucket(max(sum(p.n_blocks for p in rows), 1))
    idx, w, qid = flatten_plans(rows, fb)
    flat = jax.jit(bm25_flat_body, static_argnums=(8, 9))
    dense, _m = flat(jnp.asarray(bd), jnp.asarray(bt), jnp.asarray(idx),
                     jnp.asarray(w), jnp.asarray(qid), jnp.asarray(dl),
                     jnp.asarray(block_avg[idx]), jnp.asarray(live),
                     dl.shape[0], n_q_pad)
    return np.asarray(dense)


def _reference(dense, cand, cs, k):
    """Top-k scores and docs of the candidates re-scored off the dense
    plane, and eps, in numpy."""
    out_s, out_d, out_e = [], [], []
    for q in range(dense.shape[0]):
        order = np.argsort(cand[q], kind="stable")
        c, c_s = cand[q][order], cs[q][order]
        exact = dense[q, c]
        top = np.argsort(-exact, kind="stable")[:k]
        out_s.append(exact[top])
        out_d.append(c[top])
        both = np.isfinite(exact) & np.isfinite(c_s)
        gap = np.abs(np.where(both, exact, 0) - np.where(both, c_s, 0))
        out_e.append(np.max(gap))
    return np.stack(out_s), np.stack(out_d), np.asarray(out_e, np.float32)


@pytest.mark.parametrize("n_segs", [1, 4])
@pytest.mark.parametrize("dfs", [False, True], ids=["segment_avgdl", "dfs"])
def test_rerank_bit_equal_to_dense_plane(n_segs, dfs):
    bd, bt, dl, block_avg, refs = _plane(n_segs, seed=7 + n_segs)
    n_docs = n_segs * SEG_DOCS
    rng = np.random.default_rng(11)
    # frequent, rare and absent-in-some-segment terms; a repeated term
    queries = [["w4", "w11", "w4"], ["w1", "w2", "w5", "w9", "w36"],
               ["w37", "w20"]]
    override = 31.5 if dfs else None
    rows = _rows(refs, queries, n_docs, override)
    eff_avg = block_avg if override is None else \
        np.full_like(block_avg, override)
    n_q_pad = 4                                 # one padded query row
    kprime = 64
    live = np.zeros(dl.shape[0], bool)
    live[:n_docs] = rng.random(n_docs) > 0.05   # a few deleted docs
    dense = _dense(bd, bt, dl, live, rows, eff_avg, n_q_pad)
    # candidates: half of them docs the query matches, the rest drawn
    # from the whole shard (mostly docs it does not match); coarse
    # scores anywhere near, some -inf
    cand = np.zeros((n_q_pad, kprime), np.int32)
    for q in range(n_q_pad):
        hits = np.flatnonzero(np.isfinite(dense[q]))
        pick = rng.choice(hits, min(len(hits), kprime // 2), replace=False)
        rest = rng.permutation(np.setdiff1d(np.arange(n_docs), pick))
        cand[q] = np.concatenate([pick, rest[: kprime - len(pick)]])
    cs = rng.random((n_q_pad, kprime)).astype(np.float32) * 8
    cs[:, -5:] = -np.inf

    r = rerank_ranges(rows, n_q_pad, eff_avg)
    assert r[0].shape[1] == range_bucket(rows) >= 8
    assert (r[1] == 0).any(), "no padded (count 0) range"
    assert any(bd[s + c - 1, -1] == -1 for s, c in
               zip(r[0][r[1] > 0], r[1][r[1] > 0])), "no padded last block"
    assert any(c > 1 for c in r[1].ravel()), "no multi-block range"
    s, d, eps = _bm25_rerank_kernel(
        jnp.asarray(bd), jnp.asarray(bt), *[jnp.asarray(a) for a in r],
        jnp.asarray(dl), jnp.asarray(live), jnp.asarray(cand),
        jnp.asarray(cs), K)
    s, d, eps = np.asarray(s), np.asarray(d), np.asarray(eps)
    ref_s, ref_d, ref_e = _reference(dense, cand, cs, K)

    np.testing.assert_array_equal(s, ref_s)
    finite = np.isfinite(ref_s)
    np.testing.assert_array_equal(d[finite], ref_d[finite])
    np.testing.assert_array_equal(eps, ref_e)
    # the cases the lookup has to get right are really there
    assert finite[:3].any(axis=1).all(), "a real query matched nothing"
    assert not finite[3].any() and eps[3] == 0.0    # the padded row
    unmatched = ~np.isfinite(np.take_along_axis(dense, cand, axis=1))
    assert unmatched[:3].any(axis=1).all(), "every candidate matched"


def test_rerank_counts_lookups_per_depth_step():
    """``rerank_lookups`` grows by (ranges of the real queries) x k' at
    every depth step the coarse tier dispatched."""
    PLANES.clear()
    saved = (PLANES.enabled, PLANES.rerank_depth, PLANES.min_segments,
             PLANES.quantized)
    (PLANES.enabled, PLANES.rerank_depth, PLANES.min_segments,
     PLANES.quantized) = True, 32, 2, True
    try:
        rng = np.random.default_rng(5)
        eng = InternalEngine(
            MapperService({"properties": {"body": {"type": "text"}}}),
            shard_label="rr_lookups")
        vocab = [f"w{i}" for i in range(50)]
        for i in range(1100):
            eng.index(str(i), {"body": " ".join(
                rng.choice(vocab, size=int(rng.integers(4, 16))))})
            if i in (366, 733):
                eng.refresh()
        eng.refresh()
        reader = eng.acquire_reader()
        terms = ["w1", "w3", "w7", "w3"]
        ranges = sum(sum(1 for t in terms if t in seg.postings["body"].terms)
                     for seg in reader.segments)
        assert len(reader.segments) == 3 and ranges > len(terms)
        l0 = PLANES.stats["rerank_lookups"]
        e0 = PLANES.stats["rerank_escalations"]
        q0 = PLANES.stats["quantized_queries"]
        query_shard(reader, eng.mappers,
                    dsl.parse_query({"match": {"body": " ".join(terms)}}),
                    size=10, sort=parse_sort(None), track_total_hits=10_000)
        assert PLANES.stats["quantized_queries"] == q0 + 1
        steps = 1 + PLANES.stats["rerank_escalations"] - e0
        want = sum(ranges * 32 * 2 ** i for i in range(steps))
        assert PLANES.stats["rerank_lookups"] - l0 == want
        assert PLANES.stats_snapshot()["rerank_lookups"] >= want
    finally:
        (PLANES.enabled, PLANES.rerank_depth, PLANES.min_segments,
         PLANES.quantized) = saved
        PLANES.clear()
