"""msmarco-passage-4shard: MS MARCO passages as one BM25 index of four
primary shards, searched ``query_then_fetch``.

The generator, text, query pool and posting counts are
``msmarco-passage.py``'s, loaded from beside this file and run at the
same seed over all the index's passages, dealt into ``shards`` x
``segments`` segments of equal size: every segment holds the same
multiset of passage lengths, so a segment's average length is its
shard's. Generator doc ``g`` lies in shard ``g // shard_docs``. Its
``_id`` is routed as ``_bulk`` routes one (murmur3 of the UTF-8 ``_id``,
modulo the shard count): shard ``s`` takes, in ascending order, the first
``shard_docs`` integers whose decimal ``_id`` routes to it.

The plain reference scores each passage in numpy float64 with its own
shard's statistics (doc count, df and average length: Lucene's per-shard
collection statistics under ``query_then_fetch``), and the coordinator's
merge is by score, so its top-k is the top-k over every shard. The
control scores the same way in bfloat16 with JAX. Scores and sources are
indexed by the ``_id``'s integer.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import List

import numpy as np

from benchmark.harness import DenseScores


def _load_base():
    path = Path(__file__).with_name("msmarco-passage.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_config_msmarco-passage", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _load_base()
K1, B = base.K1, base.B

_MASK = 0xFFFFFFFF


def murmur3_decimal(n: np.ndarray) -> np.ndarray:
    """MurmurHash3 x86 32-bit (seed 0) of each non-negative integer's
    decimal string in UTF-8, as uint32: the public algorithm, vectorized
    over strings of one length at a time."""
    n = np.asarray(n, np.int64)
    text = n.astype(str)
    lens = np.char.str_len(text)
    out = np.zeros(len(n), np.uint64)

    def mul(x, c):
        return (x * np.uint64(c)) & np.uint64(_MASK)

    def rotl(x, r):
        return ((x << np.uint64(r)) | (x >> np.uint64(32 - r))) \
            & np.uint64(_MASK)

    for length in np.unique(lens).tolist():
        at = np.flatnonzero(lens == length)
        raw = np.frombuffer(text[at].astype(f"S{length}").tobytes(),
                            np.uint8).reshape(len(at), length)
        b = raw.astype(np.uint64)
        h = np.zeros(len(at), np.uint64)
        full = length // 4
        for i in range(full):
            k = b[:, 4 * i] | (b[:, 4 * i + 1] << np.uint64(8)) | \
                (b[:, 4 * i + 2] << np.uint64(16)) | \
                (b[:, 4 * i + 3] << np.uint64(24))
            k = mul(rotl(mul(k, 0xCC9E2D51), 15), 0x1B873593)
            h = rotl(h ^ k, 13)
            h = (h * np.uint64(5) + np.uint64(0xE6546B64)) & np.uint64(_MASK)
        tail = b[:, 4 * full:]
        if tail.shape[1]:
            k = np.zeros(len(at), np.uint64)
            for j in range(tail.shape[1]):
                k |= tail[:, j] << np.uint64(8 * j)
            h ^= mul(rotl(mul(k, 0xCC9E2D51), 15), 0x1B873593)
        h ^= np.uint64(length)
        h ^= h >> np.uint64(16)
        h = mul(h, 0x85EBCA6B)
        h ^= h >> np.uint64(13)
        h = mul(h, 0xC2B2AE35)
        h ^= h >> np.uint64(16)
        out[at] = h
    return out.astype(np.uint32)


def routed_ids(shards: int, per_shard: int) -> np.ndarray:
    """``[shards, per_shard]``: for each shard, the first ``per_shard``
    integers whose decimal ``_id`` routes to it, ascending."""
    total = shards * per_shard
    span = total + 8 * int(np.sqrt(total)) + 1024
    while True:
        cand = np.arange(span, dtype=np.int64)
        to = murmur3_decimal(cand) % np.uint32(shards)
        picks = [np.flatnonzero(to == s)[:per_shard] for s in range(shards)]
        if all(len(p) == per_shard for p in picks):
            return np.stack(picks)
        span *= 2


class Data(base.Data):
    def __init__(self, cfg: dict, seed: int, n_docs: int = 0):
        self.n_shards = int(cfg["shards"])
        per_seg = int(cfg["segments"])
        flat = {**cfg, "shard_docs": self.n_shards * int(cfg["shard_docs"]),
                "segments": self.n_shards * per_seg}
        super().__init__(flat, seed, n_docs=n_docs)
        self.cfg = cfg
        self.segs_per_shard = per_seg
        self.shard_docs = self.n_docs // self.n_shards
        self.ids = routed_ids(self.n_shards, self.shard_docs).reshape(-1)
        self.id_space = int(self.ids.max()) + 1
        self.doc_of_id = np.full(self.id_space, -1, np.int64)
        self.doc_of_id[self.ids] = np.arange(self.n_docs)
        self.seg_df: List = [None] * self.n_segments

    # -- the index -------------------------------------------------------

    def build_shard(self, shard: int, shard_label: str) -> list:
        """Shard ``shard``'s segments, built from the arrays on a few
        threads, with the ``_id``, seqno and names ``_bulk`` would give
        them in that shard."""
        from concurrent.futures import ThreadPoolExecutor
        first = shard * self.segs_per_shard
        with ThreadPoolExecutor(self.segs_per_shard) as pool:
            built = list(pool.map(
                lambda s: self._segment(shard_label, s),
                range(first, first + self.segs_per_shard)))
        out = []
        for j, (seg, df) in enumerate(built):
            lo = (first + j) * self.seg_docs
            ids = self.ids[lo: lo + self.seg_docs]
            seg.name = f"{shard_label}_seg{j + 1}"
            seg.ids = [str(i) for i in ids.tolist()]
            seg.id_to_doc = {d: i for i, d in enumerate(seg.ids)}
            seg.seqnos = np.arange(j * self.seg_docs,
                                   (j + 1) * self.seg_docs, dtype=np.int64)
            self.seg_df[first + j] = df
            out.append(seg)
        self.df = np.sum([d for d in self.seg_df if d is not None], axis=0)
        return out

    def source(self, doc: int):
        """The ``_source`` of the passage whose ``_id`` is ``doc``."""
        g = int(self.doc_of_id[doc]) if 0 <= doc < self.id_space else -1
        return super().source(g) if g >= 0 else None

    # -- traffic ---------------------------------------------------------

    def blocks(self, spec: dict) -> float:
        """Posting blocks of 128 the query reads in the shard that reads
        most: the mesh pads every shard's work to that one's bucket."""
        per = self.segs_per_shard
        return float(max(
            sum(max(1, -(-int(df[t]) // 128))
                for df in self.seg_df[s * per: (s + 1) * per]
                for t in spec["terms"])
            for s in range(self.n_shards)))

    def warm_class(self, spec: dict) -> int:
        """The term ranges a query's row holds in a shard (one per
        segment and term), padded as the re-rank pads them: a power of
        two, at least 8."""
        n = self.segs_per_shard * len(spec["terms"])
        return max(8, 1 << (n - 1).bit_length())

    # -- the plain reference (numpy float64, per-shard statistics) -------

    def _shard_stats(self, post: dict):
        """Doc lengths, each shard's average length, and each term's idf
        in each shard from that shard's doc count and df."""
        lens = np.diff(self.offsets).astype(np.float64)
        n = self.shard_docs
        avgdl = lens.reshape(self.n_shards, n).mean(axis=1)
        edges = np.arange(self.n_shards + 1) * n
        idf = {}
        for t, (d, _tf) in post.items():
            df = np.diff(np.searchsorted(d, edges)).astype(np.float64)
            idf[t] = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        return lens, avgdl, idf

    def _by_id(self, dense_g: np.ndarray) -> np.ndarray:
        out = np.zeros(self.id_space, dense_g.dtype)
        out[self.ids] = dense_g
        return out

    def reference(self, specs: list) -> "base.LazyRefs":
        post = self._postings([t for s in specs for t in s["terms"]])
        lens, avgdl, idf = self._shard_stats(post)
        contrib = {}
        for t, (d, tf) in post.items():
            sh = d // self.shard_docs
            contrib[t] = idf[t][sh] * tf * (K1 + 1.0) / (
                tf + K1 * (1.0 - B + B * lens[d] / avgdl[sh]))

        def one(spec):
            dense = np.zeros(self.n_docs)
            for t in spec["terms"]:
                dense[post[t][0]] += contrib[t]
            return DenseScores(self._by_id(dense))
        return base.LazyRefs(specs, one)

    def index_wide(self, specs: list, k: int = 10) -> list:
        """Top-k (``_id`` integers, scores) of each spec scored in numpy
        float64 with index-wide statistics (every passage's doc count, df
        and average length, as ``dfs_query_then_fetch`` scores): the
        fault of a program that ignores ``query_then_fetch``, put in its
        place by ``control.py --answers index_wide``."""
        post = self._postings([t for s in specs for t in s["terms"]])
        lens = np.diff(self.offsets).astype(np.float64)
        n, avgdl = float(self.n_docs), lens.mean()
        out = []
        for spec in specs:
            dense = np.zeros(self.n_docs)
            for t in spec["terms"]:
                d, tf = post[t]
                idf = np.log(1.0 + (n - len(d) + 0.5) / (len(d) + 0.5))
                dense[d] += idf * tf * (K1 + 1.0) / (
                    tf + K1 * (1.0 - B + B * lens[d] / avgdl))
            top = np.argsort(-dense, kind="stable")[:k]
            top = top[dense[top] > 0]
            out.append((self.ids[top], dense[top]))
        return out

    # -- the control: the same scoring in bfloat16 -----------------------

    def control(self, specs: list, k: int = 10) -> list:
        """Top-k (``_id`` integers, scores) of each spec, every operation
        of the reference's scoring done in bfloat16 on the default
        device."""
        import jax
        import jax.numpy as jnp
        bf = jnp.bfloat16
        post = self._postings([t for s in specs for t in s["terms"]])
        lens, avgdl, idf = self._shard_stats(post)
        n = self.n_docs

        @jax.jit
        def score(docs, w, tf, d_len, avg):
            w, tf = w.astype(bf), tf.astype(bf)
            norm = bf(K1) * (bf(1.0 - B) + bf(B) * d_len.astype(bf)
                             / avg.astype(bf))
            contrib = w * tf * bf(K1 + 1.0) / (tf + norm)
            dense = jnp.zeros(n + 1, bf).at[docs].add(contrib)
            s, i = jax.lax.top_k(dense[:n], k)
            return s.astype(jnp.float32), i

        out = []
        for spec in specs:
            docs = [post[t][0] for t in spec["terms"]]
            m = sum(len(x) for x in docs)
            pad = 1 << max(m - 1, 1).bit_length()
            d = np.concatenate(docs + [np.full(pad - m, n)]).astype(np.int32)
            sh = np.minimum(d // self.shard_docs, self.n_shards - 1)
            w = np.concatenate([idf[t][post[t][0] // self.shard_docs]
                                for t in spec["terms"]] + [np.zeros(pad - m)])
            tf = np.concatenate([post[t][1] for t in spec["terms"]]
                                + [np.zeros(pad - m)])
            dl = np.append(lens, 0.0)[d]
            s, i = score(d, w.astype(np.float32), tf.astype(np.float32),
                         dl.astype(np.float32), avgdl[sh].astype(np.float32))
            s, i = np.asarray(s), np.asarray(i)
            keep = s > 0
            out.append((self.ids[i[keep]], s[keep]))
        return out
