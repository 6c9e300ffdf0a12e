"""The BM25 coarse tier reads head terms from the dense head mirror.

A plane's heavy terms (postings over >= 1/16 of its padded docs) are
held as bf16 tf columns (``PlanePostings.head_mirror``); the coarse
kernel reads a query's head terms from them (at most ``DENSE_SLOTS``)
and scatters the rest. Against the scatter of every posting, on a
multi-segment plane whose segments differ in average length, with no,
some, all and more than ``DENSE_SLOTS`` head terms, plain, with deletes
and under a DFS avgdl override: per-segment match counts are equal,
coarse scores agree to 1e-6, and the served top-10 is bit-identical to
the exact path and agrees with a numpy float64 reference. Also the
mirror's lifecycle (breaker refusal, refresh), its counters in
``_nodes/stats``, and the mesh program's unchanged scatter body.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.index import InternalEngine
from elasticsearch_tpu.index.segment import Segment, postings_from_token_matrix
from elasticsearch_tpu.indices.breaker import BREAKERS
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.ops.bm25 import (
    DEFAULT_B, DEFAULT_K1, DENSE_SLOTS, QueryPlan, _bm25_coarse_kernel,
    bm25_coarse_body, build_query_plan, dense_slots, flatten_plans, idf,
    qb_bucket, tail_sizes,
)
from elasticsearch_tpu.ops.device_segment import (
    HEAD_DF_SHARE, MESH_PLANES, PLANES, PlanePostings,
)
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.phase import parse_sort, query_shard

VOCAB = 40
SEG_DOCS = 700
N_SEGS = 3
K = 10
CASES = ["none", "some", "all", "overflow"]
VARIANTS = ["plain", "deletes", "dfs"]


@pytest.fixture(autouse=True)
def _tier_defaults():
    PLANES.clear()
    MESH_PLANES.clear()
    saved = (PLANES.enabled, PLANES.min_segments, PLANES.rerank_depth,
             PLANES.rerank_depth_max, PLANES.quantized, PLANES.max_bytes)
    (PLANES.enabled, PLANES.min_segments, PLANES.rerank_depth,
     PLANES.rerank_depth_max, PLANES.quantized, PLANES.max_bytes) = \
        True, 2, 32, 1024, True, 0
    yield
    PLANES.clear()
    MESH_PLANES.clear()
    (PLANES.enabled, PLANES.min_segments, PLANES.rerank_depth,
     PLANES.rerank_depth_max, PLANES.quantized, PLANES.max_bytes) = saved


def _tokens(seed: int, n_segs: int = N_SEGS):
    """Per segment, a [SEG_DOCS, L] token matrix (-1 pad): zipf terms,
    segment s with longer docs than segment s - 1, so the segments'
    average lengths differ; no doc is empty."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_segs):
        width = 10 + 8 * s
        toks = np.minimum(rng.zipf(1.3, (SEG_DOCS, width)) - 1, VOCAB - 1)
        toks[rng.random(toks.shape) < 0.3] = -1
        toks[:, 0] = rng.integers(0, 30, SEG_DOCS)
        out.append(toks)
    return out


def _words(row) -> list:
    return [f"w{t}" for t in row if t >= 0]


def _query_terms(case: str, heads: list, others: list) -> list:
    """A query's terms for ``case``: no head term, two heads (one
    repeated) among other terms, only heads, more heads than DENSE_SLOTS
    beside one other term, or (term, boost) pairs that give one head
    term two weights."""
    if case == "none":
        return others[:3]
    if case == "some":
        return [heads[0], others[0], heads[3], others[1], heads[0]]
    if case == "all":
        return heads[:3]
    if case == "boosts":
        return [(heads[0], 2.0), others[0], heads[0], (heads[0], 2.0),
                (heads[1], 0.5)]
    return heads[: DENSE_SLOTS + 2] + others[:1]


# ---------------------------------------------------------------------------
# the kernel: dense + tail against the scatter of every posting
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def plane():
    segs = []
    for s, toks in enumerate(_tokens(29)):
        seg = Segment(f"dh{s}", SEG_DOCS)
        seg.postings["body"] = postings_from_token_matrix(
            toks, [f"w{i}" for i in range(VOCAB)])
        segs.append(seg)
    part = PlanePostings("body", segs)
    part.upload(part.build(None))
    return part


def _rows(part, queries, avgdl_override=None, segment_stats=False):
    """Row plans as the plane executor builds them: shard doc count and
    df (or, with ``segment_stats``, each segment's own, so a term's
    weight differs between segments), each segment's plan, block bases
    added. A query term is a term or a (term, boost) pair."""
    n = part.n_docs_total
    df = {}
    for _pos, pf, _base, _avg in part.refs:
        for t, tid in pf.terms.items():
            df[t] = df.get(t, 0) + int(pf.doc_freq[tid])
    rows = []
    for terms in queries:
        terms = [t if isinstance(t, tuple) else (t, 1.0) for t in terms]
        seg_plans = []
        for pos, pf, _base, avg in part.refs:
            if segment_stats:
                tw = [(t, bst * idf(part.segments[pos].n_docs,
                                    int(pf.doc_freq[pf.terms[t]])))
                      for t, bst in terms if t in pf.terms]
            else:
                tw = [(t, bst * idf(n, df[t])) for t, bst in terms
                      if t in pf.terms]
            impact = pf.block_max_impact(
                DEFAULT_K1, DEFAULT_B,
                avg if avgdl_override is None else avgdl_override)
            seg_plans.append(build_query_plan(tw, pf.term_blocks, impact))
        rows.append(QueryPlan.concat(
            seg_plans, idx_offsets=[b for _p, _pf, b, _a in part.refs]))
    return rows


def _coarse(part, rows, live, eff_avg, heads=None):
    """(scores per plane doc [n_q, n_docs_pad] with -inf where unmatched,
    hits [n_q, n_segs], dense slots used, tail blocks) of one coarse
    dispatch at k' = every doc: all postings scattered, or the head
    terms dense and the tail's prefix scattered."""
    tf16, dl16 = part.quantized_mirror()
    n_q = len(rows)
    fb = qb_bucket(max(sum(p.n_blocks for p in rows), 1))
    tails, dense, tail, used = rows, None, None, 0
    if heads is not None:
        tails, slot_rows, slot_w, used = dense_slots(rows, heads, n_q)
        seg_avg = np.ones(len(part.segments), np.float32)
        for pos, _pf, base, _a in part.refs:
            seg_avg[pos] = eff_avg[base]
        dense = (heads.cols, jnp.asarray(slot_rows), jnp.asarray(slot_w),
                 jnp.asarray(seg_avg))
        n_tail = sum(p.n_blocks for p in tails)
        tail = np.int32(np.searchsorted(tail_sizes(fb), n_tail))
    idx, w, qid = flatten_plans(tails, fb)
    cs, cand, hits = _bm25_coarse_kernel(
        part.block_docs, tf16, jnp.asarray(idx), jnp.asarray(w),
        jnp.asarray(qid), dl16, jnp.asarray(eff_avg[idx]),
        jnp.asarray(live), part.seg_ids(), part.n_docs_pad, n_q,
        len(part.segments), part.n_docs_pad, tail=tail, dense=dense)
    cs, cand = np.asarray(cs), np.asarray(cand)
    scores = np.full((n_q, part.n_docs_pad), -np.inf, np.float32)
    for q in range(n_q):
        scores[q, cand[q]] = cs[q]
    return scores, np.asarray(hits), used, sum(p.n_blocks for p in tails)


def test_head_terms_follow_the_rule_and_the_cap(plane):
    """Head terms are every term over 1/16 of the padded docs, heaviest
    first, and the mirror's bytes stay within the posting bytes."""
    heads = plane.head_mirror()
    df = {}
    for _pos, pf, _base, _avg in plane.refs:
        for t, tid in pf.terms.items():
            df[t] = df.get(t, 0) + int(pf.doc_freq[tid])
    floor = plane.n_docs_pad / HEAD_DF_SHARE
    assert sorted(heads.terms) == sorted(t for t in df if df[t] >= floor)
    assert [df[t] for t in heads.terms] == \
        sorted((df[t] for t in heads.terms), reverse=True)
    assert DENSE_SLOTS + 2 <= heads.n_heads < len(df)
    assert heads.cols.shape == (heads.n_heads + 1, plane.n_docs_pad)
    assert heads.cols.nbytes <= \
        plane.block_docs.nbytes + plane.block_tfs.nbytes
    assert not np.asarray(heads.cols[-1]).any()


@pytest.mark.parametrize("variant", VARIANTS + ["segment_stats"])
@pytest.mark.parametrize("case", CASES + ["boosts"])
def test_dense_coarse_matches_the_scatter(plane, case, variant):
    heads = plane.head_mirror()
    others = [f"w{i}" for i in range(VOCAB)
              if f"w{i}" not in heads.terms and any(
                  f"w{i}" in pf.terms for _p, pf, _b, _a in plane.refs)]
    terms = _query_terms(case, heads.terms, others)
    # the case's query, one query of another case, an empty row
    queries = [terms, _query_terms("some", heads.terms, others), []]
    override = 9.5 if variant == "dfs" else None
    rows = _rows(plane, queries, override,
                 segment_stats=variant == "segment_stats")
    eff_avg = plane.block_avgdl if override is None else \
        np.full_like(plane.block_avgdl, override)
    n = plane.n_docs_total
    live = np.zeros(plane.n_docs_pad, bool)
    live[:n] = True
    if variant == "deletes":
        live[:n] = np.random.default_rng(3).random(n) > 0.15

    s_scatter, h_scatter, _u, _t = _coarse(plane, rows, live, eff_avg)
    s_dense, h_dense, used, n_tail = _coarse(plane, rows, live, eff_avg,
                                            heads)

    np.testing.assert_array_equal(h_dense, h_scatter)
    matched = np.isfinite(s_scatter)
    np.testing.assert_array_equal(np.isfinite(s_dense), matched)
    np.testing.assert_allclose(s_dense[matched], s_scatter[matched],
                               rtol=1e-6)
    assert matched[0].any() and not matched[2].any()
    # the case engaged the mirror as it says
    _tails, slot_rows, _w, _n = dense_slots(rows[:1], heads, 1)
    first = int((slot_rows[0] < heads.n_heads).sum())
    tail_first = _tails[0].n_blocks
    if variant == "segment_stats":
        # a weight per segment: no term may read a column
        assert used == 0 and n_tail == sum(p.n_blocks for p in rows)
        return
    assert {"none": first == 0 and tail_first == rows[0].n_blocks,
            "some": first == 3 and 0 < tail_first < rows[0].n_blocks,
            "all": first == 3 and tail_first == 0,
            "overflow": first == DENSE_SLOTS and tail_first > 0,
            "boosts": first == 4 and 0 < tail_first}[case]
    assert used >= first and n_tail >= tail_first


def test_tail_prefix_scatters_every_tail_block(plane):
    """A batch whose tail runs to hundreds of blocks: the prefix the
    rule picks holds every tail block, and it is the smallest one."""
    heads = plane.head_mirror()
    others = [t for t in (f"w{i}" for i in range(VOCAB))
              if t not in heads.terms]
    queries = [others[i:] + heads.terms[:2] for i in range(8)]
    rows = _rows(plane, queries)
    live = np.arange(plane.n_docs_pad) < plane.n_docs_total
    s_scatter, h_scatter, _u, _t = _coarse(plane, rows, live,
                                           plane.block_avgdl)
    s_dense, h_dense, used, n_tail = _coarse(plane, rows, live,
                                             plane.block_avgdl, heads)
    fb = qb_bucket(sum(p.n_blocks for p in rows))
    sizes = tail_sizes(fb)
    pick = int(np.searchsorted(sizes, n_tail))
    assert used == 2 * len(queries) and pick >= 2
    assert sizes[pick - 1] < n_tail <= sizes[pick]
    np.testing.assert_array_equal(h_dense, h_scatter)
    matched = np.isfinite(s_scatter)
    np.testing.assert_array_equal(np.isfinite(s_dense), matched)
    np.testing.assert_allclose(s_dense[matched], s_scatter[matched],
                               rtol=1e-6)


@pytest.mark.parametrize("fb", [32, 256, 2048, 16384, 65536])
def test_tail_sizes_ladder(fb):
    sizes = tail_sizes(fb)
    assert sizes[0] == min(32, fb) and sizes[-1] == fb
    assert all(a < b for a, b in zip(sizes, sizes[1:]))
    assert all(b <= 4 * a for a, b in zip(sizes, sizes[1:]) if a >= 256)
    assert len(tail_sizes(65536)) == 6


# ---------------------------------------------------------------------------
# the served path: bit-identical to the exact path, and to numpy float64
# ---------------------------------------------------------------------------

def _engine(seed: int, label: str, n_segs: int = N_SEGS):
    toks = _tokens(seed, n_segs)
    eng = InternalEngine(
        MapperService({"properties": {"body": {"type": "text"}}}),
        shard_label=label)
    i = 0
    for seg_toks in toks:
        for row in seg_toks:
            eng.index(str(i), {"body": " ".join(_words(row))})
            i += 1
        eng.refresh()
    return eng, toks


def _reference(toks, terms, live, k, avgdl_override=None):
    """Top-k (doc, score) in numpy float64: shard doc count and df (as
    Lucene's statistics, deleted docs included), each segment's own
    average length or the override."""
    docs = [_words(r) for seg in toks for r in seg]
    n = len(docs)
    lens = np.array([len(d) for d in docs], np.float64)
    seg_of = np.repeat(np.arange(len(toks)), [len(s) for s in toks])
    avg = np.array([lens[seg_of == s].mean() for s in range(len(toks))])
    avg_doc = avg[seg_of] if avgdl_override is None else \
        np.full(n, avgdl_override)
    score = np.zeros(n)
    for t in terms:
        tf = np.array([d.count(t) for d in docs], np.float64)
        df = int((tf > 0).sum())
        w = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        norm = DEFAULT_K1 * (1.0 - DEFAULT_B + DEFAULT_B * lens / avg_doc)
        score += np.where(tf > 0, w * tf * (DEFAULT_K1 + 1) / (tf + norm),
                          0.0)
    ok = live & (score > 0)
    order = np.lexsort((np.arange(n), -score))
    top = [int(d) for d in order if ok[d]][:k]
    return top, score[top]


@pytest.fixture(scope="module")
def engines():
    eng, toks = _engine(31, "dh_plain")
    deleted, _ = _engine(31, "dh_deleted")
    gone = np.random.default_rng(4).choice(N_SEGS * SEG_DOCS, 300,
                                          replace=False)
    for d in gone:
        deleted.delete(str(int(d)))
    deleted.refresh()
    live = np.ones(N_SEGS * SEG_DOCS, bool)
    live[gone] = False
    return {"plain": (eng, toks, np.ones_like(live)),
            "deletes": (deleted, toks, live)}


def _search(eng, terms, stats=None, track=10_000):
    return query_shard(eng.acquire_reader(), eng.mappers,
                       dsl.parse_query({"match": {"body": " ".join(terms)}}),
                       size=K, sort=parse_sort(None), track_total_hits=track,
                       field_stats_overrides=stats)


def _served(r, reader):
    bases = np.cumsum([0] + [s.n_docs for s in reader.segments])
    return ([int(bases[d.segment_idx] + d.doc) for d in r.docs],
            np.array([d.score for d in r.docs], np.float32))


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("case", CASES)
def test_served_top10_exact_with_dense_heads(engines, case, variant):
    eng, toks, live = engines["deletes" if variant == "deletes"
                              else "plain"]
    reader = eng.acquire_reader()
    assert len(reader.segments) == N_SEGS
    lens = np.array([len(_words(r)) for seg in toks for r in seg])
    stats, override = None, None
    if variant == "dfs":
        stats = {"body": (float(lens.sum()) * 1.1, len(lens))}
        override = float(lens.sum()) * 1.1 / len(lens)
    PLANES.quantized = True
    _search(eng, ["w1"])                 # the plane and its mirrors
    part = next(p for p in PLANES._parts.values() if p.kind == "postings")
    heads = part.head_mirror()
    others = [f"w{i}" for i in range(VOCAB)
              if f"w{i}" not in heads.terms and any(
                  f"w{i}" in pf.terms for _p, pf, _b, _a in part.refs)]
    terms = _query_terms(case, heads.terms, others)

    d0 = PLANES.stats["coarse_dense_terms"]
    t0 = PLANES.stats["coarse_scatter_blocks"]
    q0 = PLANES.stats["quantized_queries"]
    coarse = _search(eng, terms, stats)
    assert PLANES.stats["quantized_queries"] == q0 + 1
    dense_terms = PLANES.stats["coarse_dense_terms"] - d0
    scattered = PLANES.stats["coarse_scatter_blocks"] - t0
    assert {"none": dense_terms == 0 and scattered > 0,
            "some": dense_terms > 0 and scattered > 0,
            "all": dense_terms > 0 and scattered == 0,
            "overflow": dense_terms > 0 and dense_terms % DENSE_SLOTS == 0
            and scattered > 0}[case]
    PLANES.quantized = False
    exact = _search(eng, terms, stats)

    docs, scores = _served(coarse, reader)
    exact_docs, exact_scores = _served(exact, reader)
    assert docs == exact_docs
    np.testing.assert_array_equal(scores, exact_scores)
    assert (coarse.total_hits, coarse.total_relation) == \
        (exact.total_hits, exact.total_relation)
    ref_docs, ref_scores = _reference(toks, terms, live, K, override)
    assert docs == ref_docs
    np.testing.assert_allclose(scores, ref_scores, rtol=1e-6)


# ---------------------------------------------------------------------------
# lifecycle: breaker refusal, refresh, counters in _nodes/stats
# ---------------------------------------------------------------------------

def test_breaker_refused_mirror_scatters_with_the_same_answers(monkeypatch):
    """A device breaker without room for the head mirror: the coarse
    tier scatters every posting with the same answers, counts each such
    drain, and never asks the breaker again for that plane."""
    eng, _toks = _engine(37, "dh_refused")
    terms = ["w0", "w1", "w2", "w17"]
    PLANES.quantized = False
    exact = _search(eng, terms)                 # the plane builds here
    part = next(p for p in PLANES._parts.values() if p.kind == "postings")
    part.quantized_mirror()
    asked = []
    real = part._head_terms
    monkeypatch.setattr(part, "_head_terms",
                        lambda: asked.append(1) or real())
    breaker = BREAKERS.breaker("device")
    old_limit = breaker.limit
    try:
        breaker.limit = breaker.used + 1
        PLANES.quantized = True
        r0 = PLANES.stats["coarse_dense_refused"]
        d0 = PLANES.stats["coarse_dense_terms"]
        reader = eng.acquire_reader()
        for round_ in (1, 2):
            got = _search(eng, terms)
            assert _served(got, reader)[0] == _served(exact, reader)[0]
            np.testing.assert_array_equal(_served(got, reader)[1],
                                          _served(exact, reader)[1])
            assert (got.total_hits, got.total_relation) == \
                (exact.total_hits, exact.total_relation)
            assert part.heads_refused and part.head_mirror() is None
            assert PLANES.stats["coarse_dense_refused"] >= r0 + round_
        assert PLANES.stats["coarse_dense_terms"] == d0
        assert len(asked) == 1
    finally:
        breaker.limit = old_limit


def test_refresh_builds_the_mirror_for_the_new_generation():
    """A refresh that adds a segment packs a new plane generation, whose
    head mirror is built anew over every segment, the new one's docs
    included, with the bf16 tf of each head term's postings."""
    eng, _toks = _engine(41, "dh_refresh")
    terms = ["w0", "w2", "w19"]
    _search(eng, terms)
    old = next(p for p in PLANES._parts.values() if p.kind == "postings")
    old_heads = old.head_mirror()
    rng = np.random.default_rng(43)
    for i in range(SEG_DOCS):
        eng.index(f"new{i}", {"body": " ".join(
            f"w{t}" for t in np.minimum(rng.zipf(1.3, 20) - 1, VOCAB - 1))})
    eng.refresh()
    d0 = PLANES.stats["coarse_dense_terms"]
    coarse = _search(eng, terms)
    assert PLANES.stats["coarse_dense_terms"] > d0
    new = next(p for p in PLANES._parts.values()
               if p.kind == "postings" and p is not old)
    heads = new.head_mirror()
    assert len(new.segments) == N_SEGS + 1
    assert heads is not old_heads
    assert heads.cols.shape == (heads.n_heads + 1, new.n_docs_pad)
    cols = np.asarray(heads.cols).astype(np.float32)
    for h, term in enumerate(heads.terms):
        want = np.zeros(new.n_docs_pad, np.float32)
        for pos, pf, _base, _avg in new.refs:
            docs, tfs = pf.postings_for(term)
            want[new.doc_base[pos] + docs] = \
                tfs.astype(jnp.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(cols[h], want)
    assert (cols[:-1, new.doc_base[N_SEGS]:new.n_docs_total] > 0).any()
    PLANES.quantized = False
    exact = _search(eng, terms)
    reader = eng.acquire_reader()
    assert _served(coarse, reader)[0] == _served(exact, reader)[0]
    np.testing.assert_array_equal(_served(coarse, reader)[1],
                                  _served(exact, reader)[1])


def test_counters_in_nodes_stats():
    """``coarse_dense_terms`` grows by the query's head terms and
    ``coarse_scatter_blocks`` by its other terms' blocks, at every depth
    step, and both appear in ``_nodes/stats``."""
    from elasticsearch_tpu.testing import InProcessCluster
    c = InProcessCluster(n_nodes=1, seed=47)
    c.start()
    try:
        client = c.client()
        resp, err = c.call(lambda cb: client.create_index("dh", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {"body": {"type": "text"}}}}, cb))
        assert err is None, err
        c.ensure_green("dh")
        i = 0
        for seg_toks in _tokens(53):
            items = [{"action": "index", "index": "dh", "id": str(i + j),
                      "source": {"body": " ".join(_words(row))}}
                     for j, row in enumerate(seg_toks)]
            i += len(items)
            resp, err = c.call(lambda cb: client.bulk(items, cb))
            assert err is None and not resp.get("errors"), resp
            c.call(lambda cb: client.refresh("dh", cb))

        def section():
            stats = client.nodes_stats()["nodes"]
            return next(iter(stats.values()))["device_plane"]

        resp, err = c.call(lambda cb: client.search(
            "dh", {"query": {"match": {"body": "w1"}}}, cb))
        assert err is None, err
        part = next(p for p in PLANES._parts.values()
                    if p.kind == "postings")
        heads = part.head_mirror()
        assert heads is not None
        others = [f"w{i}" for i in range(VOCAB)
                  if f"w{i}" not in heads.terms and any(
                      f"w{i}" in pf.terms for _p, pf, _b, _a in part.refs)]
        terms = heads.terms[:2] + others[:2]
        tail = sum(pf.term_blocks(t)[1] for t in others[:2]
                   for _p, pf, _b, _a in part.refs)
        before = section()
        resp, err = c.call(lambda cb: client.search(
            "dh", {"query": {"match": {"body": " ".join(terms)}}}, cb))
        assert err is None, err
        after = section()
        steps = 1 + after["rerank_escalations"] - \
            before["rerank_escalations"]
        assert after["quantized_queries"] == before["quantized_queries"] + 1
        assert after["coarse_dense_terms"] - \
            before["coarse_dense_terms"] == 2 * steps
        assert after["coarse_scatter_blocks"] - \
            before["coarse_scatter_blocks"] == tail * steps
        assert after["coarse_dense_refused"] == before["coarse_dense_refused"]
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# the mesh program keeps the scatter of every posting
# ---------------------------------------------------------------------------

def _scatter_body(block_docs, block_tfs_q, flat_idx, flat_w, flat_q,
                  doc_lens_q, flat_avgdl, live, seg_ids, n_docs_pad, n_q,
                  n_segs, kprime, k1=DEFAULT_K1, b=DEFAULT_B):
    """The coarse body as it was before the dense head mirror: gather
    every block of the flat plan and scatter-add its contributions."""
    docs = block_docs[flat_idx]
    tfs = block_tfs_q[flat_idx]
    valid = docs >= 0
    safe = jnp.where(valid, docs, 0)
    dl = doc_lens_q[safe]
    h = jnp.bfloat16
    norm = h(k1) * (h(1.0 - b) + h(b) * dl
                    / flat_avgdl.astype(h)[:, None])
    contrib = flat_w.astype(h)[:, None] * tfs * h(k1 + 1.0) \
        / (tfs + norm)
    contrib = jnp.where(valid, contrib.astype(jnp.float32), 0.0)
    tgt = flat_q[:, None] * n_docs_pad + safe
    scores = jnp.zeros((n_q * n_docs_pad,), jnp.float32)
    scores = scores.at[tgt.reshape(-1)].add(contrib.reshape(-1),
                                            mode="drop")
    scores = scores.reshape(n_q, n_docs_pad)
    matched = live[None, :] & (scores > 0.0)
    scores = jnp.where(matched, scores, -jnp.inf)
    cs, cand = jax.lax.top_k(scores, kprime)
    onehot = jax.nn.one_hot(seg_ids, n_segs, dtype=jnp.int32)
    hits = matched.astype(jnp.int32) @ onehot
    return cs, cand, hits


def test_mesh_coarse_outputs_unchanged():
    """The mesh program's per-slot body (``bm25_coarse_body`` without the
    dense operands) and the single plane's scatter-only dispatch give
    bit for bit what the pre-mirror scatter body gives."""
    from elasticsearch_tpu.parallel.mesh import make_mesh, mesh_bm25_coarse
    parts, flats = [], []
    for shard in range(2):
        segs = []
        for s, toks in enumerate(_tokens(59 + shard, n_segs=2)):
            seg = Segment(f"mesh{shard}_{s}", SEG_DOCS)
            seg.postings["body"] = postings_from_token_matrix(
                toks, [f"w{i}" for i in range(VOCAB)])
            segs.append(seg)
        part = PlanePostings("body", segs)
        part.upload(part.build(None))
        parts.append(part)
    queries = [["w0", "w3", "w3", "w21"], ["w1", "w30"]]
    n_q, kprime = 2, 64
    nb = max(p.block_docs.shape[0] for p in parts)
    fb = qb_bucket(max(sum(r.n_blocks for r in _rows(p, queries))
                       for p in parts))
    stacked = {k: [] for k in ("bd", "btq", "dlq", "fi", "fw", "fq", "fa",
                               "lv", "si")}
    want = []
    for part in parts:
        tf16, dl16 = part.quantized_mirror()
        idx, w, qid = flatten_plans(_rows(part, queries), fb)
        live = np.arange(part.n_docs_pad) < part.n_docs_total
        args = (part.block_docs, tf16, jnp.asarray(idx), jnp.asarray(w),
                jnp.asarray(qid), dl16,
                jnp.asarray(part.block_avgdl[idx]), jnp.asarray(live),
                part.seg_ids())
        ref = jax.jit(_scatter_body, static_argnums=(9, 10, 11, 12))(
            *args, part.n_docs_pad, n_q, 2, kprime)
        got = _bm25_coarse_kernel(*args, part.n_docs_pad, n_q, 2, kprime)
        body = jax.jit(bm25_coarse_body, static_argnums=(9, 10, 11, 12))(
            *args, part.n_docs_pad, n_q, 2, kprime)
        for a, b_, c_ in zip(ref, got, body):
            np.testing.assert_array_equal(np.asarray(b_), np.asarray(a))
            np.testing.assert_array_equal(np.asarray(c_), np.asarray(a))
        want.append(ref)
        pad = nb - part.block_docs.shape[0]
        stacked["bd"].append(jnp.pad(part.block_docs, ((0, pad), (0, 0)),
                                     constant_values=-1))
        stacked["btq"].append(jnp.pad(tf16, ((0, pad), (0, 0))))
        for key, a in zip(("dlq", "fi", "fw", "fq", "fa", "lv", "si"),
                          (dl16, *args[2:5], args[6], args[7], args[8])):
            stacked[key].append(a)
    assert parts[0].n_docs_pad == parts[1].n_docs_pad
    fn = mesh_bm25_coarse(make_mesh(2, 1, jax.devices()[:2]),
                          parts[0].n_docs_pad, n_q, kprime, 2,
                          DEFAULT_K1, DEFAULT_B)
    out = fn(*(jnp.stack(stacked[k]) for k in
               ("bd", "btq", "dlq", "fi", "fw", "fq", "fa", "lv", "si")))
    for slot in range(2):
        for a, b_ in zip(want[slot], out):
            np.testing.assert_array_equal(np.asarray(b_)[slot],
                                          np.asarray(a))
