import numpy as np
import pytest

from elasticsearch_tpu.index.segment import BLOCK, SegmentBuilder, merge_segments, next_pow2
from elasticsearch_tpu.mapping import MapperService


MAPPING = {
    "properties": {
        "body": {"type": "text"},
        "tag": {"type": "keyword"},
        "n": {"type": "long"},
        "v": {"type": "dense_vector", "dims": 3, "similarity": "dot_product"},
        "feats": {"type": "rank_features"},
    }
}


def build_segment(docs, name="s1"):
    svc = MapperService(MAPPING)
    b = SegmentBuilder(name, svc)
    for i, src in enumerate(docs):
        b.add(svc.parse_document(str(i), src), seqno=i, version=1)
    return b.build(), svc


def test_next_pow2():
    assert next_pow2(1) == 1
    assert next_pow2(5) == 8
    assert next_pow2(128) == 128
    assert next_pow2(129) == 256


def test_postings_structure():
    seg, _ = build_segment([
        {"body": "fox jumps fox"},
        {"body": "lazy dog"},
        {"body": "fox dog"},
    ])
    pf = seg.postings["body"]
    docs, tfs = pf.postings_for("fox")
    assert docs.tolist() == [0, 2]
    assert tfs.tolist() == [2.0, 1.0]
    assert pf.doc_freq[pf.terms["fox"]] == 2
    assert pf.doc_lens.tolist() == [3.0, 2.0, 2.0]
    assert pf.block_docs.shape[1] == BLOCK
    # padding is -1
    start, count = pf.term_blocks("fox")
    block = pf.block_docs[start]
    assert block[2] == -1


def test_positions():
    seg, _ = build_segment([{"body": "a b a c"}])
    pf = seg.postings["body"]
    assert pf.positions_for("a", 0).tolist() == [0, 2]
    assert pf.positions_for("c", 0).tolist() == [3]
    assert pf.positions_for("z", 0).tolist() == []


def test_keywords_and_docvalues():
    seg, _ = build_segment([
        {"tag": ["x", "y"], "n": 5},
        {"tag": "x", "n": 7},
        {},
    ])
    kf = seg.keywords["tag"]
    assert kf.docs_with_term("x").tolist() == [0, 1]
    assert kf.docs_with_term("y").tolist() == [0]
    dv = seg.doc_values["n"]
    assert dv.values[:2].tolist() == [5, 7]
    assert dv.exists.tolist() == [True, True, False]
    assert dv.values.dtype == np.int64


def test_vectors_and_features():
    seg, _ = build_segment([
        {"v": [1.0, 0.0, 0.0], "feats": {"a": 2.0}},
        {"feats": {"a": 1.0, "b": 3.0}},
    ])
    vf = seg.vectors["v"]
    assert vf.matrix.shape == (2, 3)
    assert vf.exists.tolist() == [True, False]
    assert vf.norms[0] == pytest.approx(1.0)
    ff = seg.features["feats"]
    start, count = ff.feature_blocks("a")
    docs = ff.block_docs[start:start + count].reshape(-1)
    assert docs[docs >= 0].tolist() == [0, 1]


def test_many_docs_multi_block():
    n = 300  # > 2 blocks of 128
    seg, _ = build_segment([{"body": "common"} for _ in range(n)])
    pf = seg.postings["common" and "body"]
    docs, tfs = pf.postings_for("common")
    assert len(docs) == n
    assert docs.tolist() == list(range(n))
    start, count = pf.term_blocks("common")
    assert count == 3


def test_delete_and_live_mask():
    seg, _ = build_segment([{"body": "a"}, {"body": "b"}])
    assert seg.live_count == 2
    seg.delete_doc(0)
    assert seg.live_count == 1
    assert seg.doc_for_id("0") is None
    assert seg.doc_for_id("1") == 1


def test_merge_purges_deletes_and_remaps():
    seg1, svc = build_segment([
        {"body": "fox one", "tag": "a", "n": 1, "v": [1, 0, 0], "feats": {"f": 1.0}},
        {"body": "fox two", "tag": "b", "n": 2},
    ], "s1")
    b2 = SegmentBuilder("s2", svc)
    b2.add(svc.parse_document("2", {"body": "fox three", "tag": "a", "n": 3,
                                    "v": [0, 1, 0], "feats": {"f": 2.0}}), seqno=2)
    seg2 = b2.build()
    seg1.delete_doc(1)

    merged = merge_segments("m1", [seg1, seg2], svc)
    assert merged.n_docs == 2
    assert merged.ids == ["0", "2"]
    pf = merged.postings["body"]
    docs, _ = pf.postings_for("fox")
    assert docs.tolist() == [0, 1]
    docs_two, _ = pf.postings_for("two")
    assert len(docs_two) == 0  # deleted doc's term gone... (term present, no docs)
    assert merged.doc_values["n"].values.tolist() == [1, 3]
    assert merged.keywords["tag"].docs_with_term("a").tolist() == [0, 1]
    assert merged.vectors["v"].matrix[1].tolist() == [0.0, 1.0, 0.0]
    # positions survive merge
    assert pf.positions_for("three", 1).tolist() == [1]
    assert merged.seqnos.tolist() == [0, 2]


def test_postings_from_token_matrix_matches_builder():
    """Vectorized bulk postings == per-doc SegmentBuilder postings."""
    import numpy as np
    from elasticsearch_tpu.index.segment import (
        SegmentBuilder, postings_from_token_matrix,
    )
    from elasticsearch_tpu.mapping import MapperService
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, 30, size=(500, 9)).astype(np.int64)
    tokens[rng.random(size=tokens.shape) < 0.2] = -1   # ragged doc lengths
    pf = postings_from_token_matrix(tokens)

    svc = MapperService({"properties": {"body": {"type": "text"}}})
    b = SegmentBuilder("s", svc)
    for i, row in enumerate(tokens):
        body = " ".join(f"t{z}" for z in row if z >= 0) or "tpad"
        b.add(svc.parse_document(str(i), {"body": body}), seqno=i)
    ref = b.build().postings["body"]
    for term in [f"t{i}" for i in range(30)]:
        d1, f1 = pf.postings_for(term)
        d2, f2 = ref.postings_for(term)
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(f1, f2)
    assert pf.doc_freq[:30].tolist() == ref.doc_freq[
        [ref.terms[f"t{i}"] for i in range(30)]].tolist()


@pytest.mark.parametrize("seed", [0, 1])
def test_built_and_merged_blocks_match_plain_reference(seed):
    """The array-built postings and feature blocks (and the merge that
    re-packs them): every term's docs, tfs and per-doc positions, and
    every feature's docs and weights, equal a plain dict reference."""
    rng = np.random.default_rng(seed)
    docs = []
    for _ in range(400):
        src = {}
        if rng.random() < 0.9:
            src["body"] = " ".join(f"w{int(x)}" for x in rng.integers(
                0, 40, int(rng.integers(0, 30))))
        if rng.random() < 0.7:
            src["feats"] = {f"f{int(k)}": float(k) + 0.5 for k in
                            rng.integers(0, 20, int(rng.integers(1, 6)))}
        docs.append(src)
    want_pos, want_feat = {}, {}
    for i, src in enumerate(docs):
        for pos, w in enumerate(src.get("body", "").split()):
            want_pos.setdefault(w, {}).setdefault(i, []).append(pos)
        for k, v in src.get("feats", {}).items():
            want_feat.setdefault(k, {})[i] = v

    def check(seg, order):
        """``order``: the source doc index of each local doc."""
        pf, ff = seg.postings["body"], seg.features["feats"]
        for w, per in want_pos.items():
            local = [(j, per[o]) for j, o in enumerate(order) if o in per]
            d, tf = pf.postings_for(w)
            assert d.tolist() == [j for j, _ in local], w
            assert tf.tolist() == [len(p) for _, p in local], w
            for j, p in local:
                assert pf.positions_for(w, j).tolist() == p, (w, j)
        for k, per in want_feat.items():
            start, count = ff.feature_blocks(k)
            fd = ff.block_docs[start:start + count].reshape(-1)
            fw = ff.block_weights[start:start + count].reshape(-1)
            assert fd[fd >= 0].tolist() == [j for j, o in enumerate(order)
                                            if o in per], k
            assert fw[fd >= 0].tolist() == [per[o] for o in order
                                            if o in per], k

    seg1, svc = build_segment(docs[:250], "s1")
    b2 = SegmentBuilder("s2", svc)
    for i, src in enumerate(docs[250:], start=250):
        b2.add(svc.parse_document(str(i), src), seqno=i)
    seg2 = b2.build()
    check(seg1, list(range(250)))
    check(seg2, list(range(250, 400)))
    for seg, base in ((seg1, 0), (seg2, 250)):
        for local in range(seg.n_docs):
            if (base + local) % 5 == 0:
                seg.delete_doc(local)
    merged = merge_segments("m", [seg1, seg2], svc)
    check(merged, [i for i in range(400) if i % 5])
