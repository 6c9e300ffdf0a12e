"""msmarco-passage: a shard of MS MARCO passages, served by BM25.

The generator (copied from ``chip_smoke.py`` and cut to this
configuration's one field) makes from a seed the passages' token ids:
zipf over ``vocab`` terms, gamma lengths around ``mean_len``. Every
segment holds the same multiset of passage lengths, each in its own
seeded order, so a segment's average length is the shard's. Segments are
built from those arrays, with the ``_source`` and ``_id`` that ``_bulk``
would store. Queries draw their terms from the passages' own term
distribution, the most frequent terms included. The plain reference
scores the same arrays in numpy float64 with the shard's statistics (doc
count, df and average length, as Lucene's collection statistics give
them); the control scores them in bfloat16 with JAX.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from benchmark.harness import DenseScores, new_segment

K1, B = 1.2, 0.75


def inverse_cdf(cdf: np.ndarray, u: np.ndarray) -> np.ndarray:
    """int32 ``searchsorted(cdf, u)``, in slices on a few threads (the
    search releases the interpreter lock)."""
    from concurrent.futures import ThreadPoolExecutor
    flat = u.reshape(-1)
    out = np.empty(flat.shape, np.int32)
    step = -(-len(flat) // 8)

    def part(a):
        out[a: a + step] = np.searchsorted(cdf, flat[a: a + step])
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(part, range(0, len(flat), step)))
    return out.reshape(u.shape)


class LazyRefs:
    """``refs[i]``: the reference scores of ``specs[i]``, computed when
    asked for and not kept, so one dense score vector lives at a time."""

    def __init__(self, specs: Sequence[dict], one: Callable):
        self.specs, self.one = specs, one

    def __len__(self) -> int:
        return len(self.specs)

    def __getitem__(self, i: int):
        return self.one(self.specs[i])


class Data:
    def __init__(self, cfg: dict, seed: int, n_docs: int = 0):
        self.cfg = cfg
        self.index = cfg["index"]
        n = self.n_docs = int(n_docs or cfg["shard_docs"])
        self.n_segments = int(cfg["segments"])
        if n % self.n_segments:
            raise ValueError(f"{n} docs do not split into "
                             f"{self.n_segments} equal segments")
        self.seg_docs = n // self.n_segments
        rng = np.random.default_rng([seed, 1])
        v = self.vocab = int(cfg["vocab"])
        lens = self.lengths(rng)
        self.offsets = np.zeros(n + 1, np.int64)
        np.cumsum(lens, out=self.offsets[1:])
        cdf = np.cumsum(1.0 / np.arange(1, v + 1) ** cfg["zipf_exponent"])
        self.cdf = cdf / cdf[-1]
        self.tokens = inverse_cdf(self.cdf, rng.random(int(self.offsets[-1])))
        self.doc_of = np.repeat(np.arange(n, dtype=np.int32), lens)
        self.words = [f"w{t}" for t in range(v)]

    def lengths(self, rng) -> np.ndarray:
        """Passage lengths: gamma(4) draws for one segment's docs, dealt
        to every segment in a seeded order of its own."""
        cfg = self.cfg
        one = np.clip(np.rint(rng.gamma(4.0, float(cfg["mean_len"]) / 4.0,
                                        self.seg_docs)),
                      cfg["min_len"], cfg["max_len"]).astype(np.int64)
        return np.concatenate([rng.permutation(one)
                               for _ in range(self.n_segments)])

    # -- the index -------------------------------------------------------

    def index_body(self) -> dict:
        return {"settings": self.cfg["settings"],
                "mappings": self.cfg["mapping"]}

    def source(self, doc: int) -> dict:
        toks = self.tokens[self.offsets[doc]: self.offsets[doc + 1]]
        return {"body": " ".join(self.words[t] for t in toks.tolist())}

    def _texts(self, lo: int, hi: int) -> List[str]:
        """Passage texts of docs [lo, hi): the words of the tokens, one
        space apart, gathered as bytes in chunks."""
        wb = np.frombuffer("".join(w + " " for w in self.words).encode(),
                           np.uint8)
        wlen = np.array([len(w) + 1 for w in self.words], np.int64)
        woff = np.zeros(len(wlen), np.int64)
        np.cumsum(wlen[:-1], out=woff[1:])
        out: List[str] = []
        for a in range(lo, hi, 1 << 16):
            b = min(hi, a + (1 << 16))
            toks = self.tokens[self.offsets[a]: self.offsets[b]]
            ln = wlen[toks]
            ends = np.cumsum(ln)
            idx = np.repeat(woff[toks] - (ends - ln), ln) + \
                np.arange(ends[-1])
            buf = wb[idx].tobytes().decode("ascii")
            tok_end = self.offsets[a + 1: b + 1] - self.offsets[a]
            char_end = ends[tok_end - 1]
            char_start = np.concatenate([[0], char_end[:-1]])
            out.extend(buf[s: e - 1] for s, e in
                       zip(char_start.tolist(), char_end.tolist()))
        return out

    def build_segments(self, shard_label: str) -> list:
        """The segments, built from the arrays on a few threads (numpy
        releases the interpreter lock in its sorts and gathers)."""
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(self.n_segments) as pool:
            built = list(pool.map(
                lambda s: self._segment(shard_label, s),
                range(self.n_segments)))
        self.seg_df = [df for _seg, df in built]
        self.df = np.sum(self.seg_df, axis=0)
        return [seg for seg, _df in built]

    def _segment(self, shard_label: str, s: int):
        from elasticsearch_tpu.index.segment import postings_from_token_matrix
        lo, hi = s * self.seg_docs, (s + 1) * self.seg_docs
        sources = [{"body": t} for t in self._texts(lo, hi)]
        seg = new_segment(f"{shard_label}_seg{s + 1}", lo, hi, sources)
        # body: the dense [docs, max_len] token matrix
        a, b = self.offsets[lo], self.offsets[hi]
        lens = np.diff(self.offsets[lo: hi + 1])
        mat = np.full((hi - lo, int(lens.max())), -1, np.int32)
        rows = self.doc_of[a:b] - lo
        cols = np.arange(b - a) - (self.offsets[lo:hi] - a)[rows]
        mat[rows, cols] = self.tokens[a:b]
        top = int(mat.max()) + 1
        seg.postings["body"] = postings_from_token_matrix(
            mat, self.words[:top])
        srt = np.sort(mat, axis=1)
        first = np.ones_like(srt, bool)
        first[:, 1:] = srt[:, 1:] != srt[:, :-1]
        first &= srt >= 0
        df = np.bincount(srt[first], minlength=self.vocab)
        return seg, df

    # -- traffic ---------------------------------------------------------

    def pool(self, traffic: dict, seed: int) -> list:
        """``pool`` match queries: ``min`` + Poisson(``mean`` - ``min``)
        distinct terms (at most ``max``), each drawn by its frequency in
        the passages."""
        if traffic["class"] != "bm25":
            raise ValueError(f"msmarco-passage serves no "
                             f"{traffic['class']!r}")
        rng = np.random.default_rng([seed, 2])
        q = traffic["terms"]
        sizes = np.minimum(q["min"] + rng.poisson(q["mean"] - q["min"],
                                                  int(traffic["pool"])),
                           q["max"])
        out = []
        for want in sizes.tolist():
            terms: List[int] = []
            while len(terms) < want:
                t = int(np.searchsorted(self.cdf, rng.random()))
                if t not in terms:
                    terms.append(t)
            out.append({"terms": terms})
        return out

    def body(self, spec: dict) -> dict:
        return {"size": 10, "request_cache": False,
                "query": {"match": {"body": " ".join(
                    self.words[t] for t in spec["terms"])}}}

    def blocks(self, spec: dict) -> float:
        """Posting blocks of 128 (Lucene's block size) the query reads,
        at least one per term and segment: what a drain's shapes grow
        with."""
        return float(sum(max(1, -(-int(df[t]) // 128))
                         for df in self.seg_df for t in spec["terms"]))

    def work(self, spec: dict) -> float:
        """Postings the query reads: its terms' doc counts."""
        return float(sum(int(self.df[t]) for t in spec["terms"]))

    # -- the plain reference (numpy float64) -----------------------------

    def _postings(self, terms: Sequence[int]) -> Dict[int, Tuple]:
        """term -> (docs ascending, tf) for each of ``terms``, from one
        pass over the tokens: a stable sort by the term's rank among
        ``terms`` keeps each term's docs in order, and a (term, doc) run
        is one posting."""
        terms = sorted(set(terms))
        rank = np.full(self.vocab, -1, np.int32)
        rank[terms] = np.arange(len(terms))
        r = rank[self.tokens]
        hit = np.flatnonzero(r >= 0)
        r = r[hit]
        if len(terms) < (1 << 16):
            r = r.astype(np.uint16)         # a radix sort
        order = np.argsort(r, kind="stable")
        r, d = r[order], self.doc_of[hit[order]]
        del hit, order
        new = np.ones(len(r), bool)
        new[1:] = (r[1:] != r[:-1]) | (d[1:] != d[:-1])
        starts = np.flatnonzero(new)
        tf = np.diff(np.append(starts, len(r))).astype(np.float64)
        r, d = r[starts], d[starts]
        cuts = np.searchsorted(r, np.arange(len(terms) + 1))
        return {t: (d[cuts[j]: cuts[j + 1]], tf[cuts[j]: cuts[j + 1]])
                for j, t in enumerate(terms)}

    def _stats(self, post: dict):
        """Doc lengths, the shard's average length, and each term's idf
        from the shard's doc count and df."""
        lens = np.diff(self.offsets).astype(np.float64)
        n = self.n_docs
        idf = {t: np.log(1.0 + (n - len(d) + 0.5) / (len(d) + 0.5))
               for t, (d, _tf) in post.items()}
        return lens, float(lens.mean()), idf

    def reference(self, specs: list) -> LazyRefs:
        post = self._postings([t for s in specs for t in s["terms"]])
        lens, avgdl, idf = self._stats(post)
        contrib = {}
        for t, (d, tf) in post.items():
            contrib[t] = idf[t] * tf * (K1 + 1.0) / (
                tf + K1 * (1.0 - B + B * lens[d] / avgdl))

        def one(spec):
            dense = np.zeros(self.n_docs)
            for t in spec["terms"]:
                dense[post[t][0]] += contrib[t]
            return DenseScores(dense)
        return LazyRefs(specs, one)

    # -- the control: the same scoring in bfloat16 -----------------------

    def control(self, specs: list, k: int = 10) -> list:
        """Top-k (docs, scores) of each spec, every operation of the
        reference's scoring done in bfloat16 on the default device."""
        import jax
        import jax.numpy as jnp
        bf = jnp.bfloat16
        post = self._postings([t for s in specs for t in s["terms"]])
        lens, avgdl, idf = self._stats(post)
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
            w = np.concatenate([np.full(len(post[t][0]), idf[t])
                                for t in spec["terms"]] + [np.zeros(pad - m)])
            tf = np.concatenate([post[t][1] for t in spec["terms"]]
                                + [np.zeros(pad - m)])
            dl = np.append(lens, 0.0)[d]
            s, i = score(d, w.astype(np.float32), tf.astype(np.float32),
                         dl.astype(np.float32), np.float32(avgdl))
            s, i = np.asarray(s), np.asarray(i)
            keep = s > 0
            out.append((i[keep].astype(np.int64), s[keep]))
        return out
