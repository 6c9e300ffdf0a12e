"""sift1m: ann-benchmarks sift-128-euclidean at its published size.

The generator (copied from ``chip_smoke.py``) makes SIFT-like vectors
from a seed: 1024 gamma centres plus normal noise, rounded and clipped to
[0, 255], so every component is an integer (exact in bfloat16 and
float32). One segment, as a vector track force-merges before querying.
The plain reference computes squared distances exactly with JAX (integer
dot products, whose partial sums stay under 2^24, are exact in float32)
and the score ``1 / (1 + distance)`` in float64; the control computes
them in bfloat16.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import new_segment

BLOCK_QUERIES = 16      # rows per top-k over 10^6 docs, as the kNN kernel's


class KnnScores:
    """Exact reference scores of one query: its top-k, and any doc's."""

    def __init__(self, data: "Data", q: np.ndarray, top: np.ndarray):
        self.data, self.q, self._top = data, q, top

    def top(self, k: int) -> np.ndarray:
        return self._top[:k]

    def score(self, doc: int) -> float:
        x = self.data.vectors[doc].astype(np.int64)
        d2 = int(np.dot(x - self.q, x - self.q))
        return 1.0 / (1.0 + np.sqrt(d2))


class Data:
    def __init__(self, cfg: dict, seed: int, n_docs: int = 0):
        self.cfg = cfg
        self.index = cfg["index"]
        n = self.n_docs = int(n_docs or cfg["docs"])
        dims = self.dims = int(cfg["dims"])
        rng = np.random.default_rng([seed, 1])
        self.centers = rng.gamma(0.7, 30.0, (int(cfg["clusters"]), dims))
        self.vectors = np.empty((n, dims), np.uint8)
        for a in range(0, n, 1 << 16):
            b = min(n, a + (1 << 16))
            x = self.centers[rng.integers(0, len(self.centers), b - a)] + \
                rng.normal(0.0, 9.0, (b - a, dims))
            self.vectors[a:b] = np.clip(np.rint(x), 0, 255)

    # -- the index -------------------------------------------------------

    def index_body(self) -> dict:
        return {"settings": self.cfg["settings"],
                "mappings": self.cfg["mapping"]}

    def source(self, doc: int):
        return None             # queries ask for no _source

    def build_segments(self, shard_label: str) -> list:
        from elasticsearch_tpu.index.segment import VectorField
        n = self.n_docs
        seg = new_segment(f"{shard_label}_seg1", 0, n,
                          [{} for _ in range(n)])
        matrix = self.vectors.astype(np.float32)
        seg.vectors["vec"] = VectorField(
            matrix=matrix, exists=np.ones(n, bool),
            norms=np.linalg.norm(matrix, axis=1).astype(np.float32),
            similarity="l2_norm", dims=self.dims)
        return [seg]

    # -- traffic ---------------------------------------------------------

    def pool(self, traffic: dict, seed: int) -> list:
        if traffic["class"] != "knn":
            raise ValueError(f"sift1m serves no {traffic['class']!r}")
        rng = np.random.default_rng([seed, 2])
        n = int(traffic["pool"])
        x = self.centers[rng.integers(0, len(self.centers), n)] + \
            rng.normal(0.0, 9.0, (n, self.dims))
        vecs = np.clip(np.rint(x), 0, 255).astype(np.int64)
        return [{"vector": v} for v in vecs.tolist()]

    def body(self, spec: dict) -> dict:
        return {"size": 10, "request_cache": False, "_source": False,
                "query": {"knn": {"field": "vec",
                                  "query_vector": spec["vector"],
                                  "k": 10, "num_candidates": 100}}}

    def blocks(self, spec: dict) -> float:
        return 1.0              # every query scans the whole matrix

    def work(self, spec: dict) -> float:
        return 1.0

    # -- the plain reference ---------------------------------------------

    def reference(self, specs: list, k: int = 10) -> list:
        """Exact top-k squared distances on the default device: integer
        components make the float32 dot products exact at the highest
        precision (every partial sum stays under 2^24), and the rest is
        int32 arithmetic; the scores are then taken in float64."""
        import jax
        import jax.numpy as jnp
        x = jnp.asarray(self.vectors)
        xx = jnp.sum(x.astype(jnp.int32) ** 2, axis=1)

        @jax.jit
        def top(q, x, xx):
            dots = jax.lax.dot_general(
                q.astype(jnp.float32), x.astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            d2 = xx[None, :] + jnp.sum(q * q, axis=1)[:, None] - \
                2 * dots.astype(jnp.int32)
            # candidates by float32 (exact below 2^24, where the nearest
            # lie); their exact distances are taken on the host
            _, idx = jax.lax.top_k(-d2.astype(jnp.float32), k + 16)
            return idx

        out = []
        for qa in range(0, len(specs), BLOCK_QUERIES):
            chunk = specs[qa: qa + BLOCK_QUERIES]
            q = np.zeros((BLOCK_QUERIES, self.dims), np.int32)
            q[: len(chunk)] = [s["vector"] for s in chunk]
            idx = np.asarray(top(jnp.asarray(q), x, xx))
            diff = self.vectors[idx].astype(np.int64) - q[:, None, :]
            best = np.sort(np.einsum("qcd,qcd->qc", diff, diff), axis=1)
            scores = 1.0 / (1.0 + np.sqrt(best[:, :k].astype(np.float64)))
            out.extend(KnnScores(self, q[j].astype(np.int64), scores[j])
                       for j in range(len(chunk)))
        return out

    # -- the control: the same scoring in bfloat16 -----------------------

    def control(self, specs: list, k: int = 10) -> list:
        """Top-k (docs, scores) of each spec, every operation of the
        reference's scoring done in bfloat16 on the default device."""
        import jax
        import jax.numpy as jnp
        bf = jnp.bfloat16
        x = jnp.asarray(self.vectors, bf)
        xx = jnp.sum(x * x, axis=1, dtype=bf)

        @jax.jit
        def top(q, x, xx):
            q = q.astype(bf)
            dots = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                                       preferred_element_type=bf)
            qq = jnp.sum(q * q, axis=1, dtype=bf)
            d2 = jnp.maximum(xx[None, :] + qq[:, None] - bf(2) * dots, bf(0))
            s = bf(1) / (bf(1) + jnp.sqrt(d2))
            s, i = jax.lax.top_k(s, k)
            return s.astype(jnp.float32), i

        out = []
        for qa in range(0, len(specs), BLOCK_QUERIES):
            chunk = specs[qa: qa + BLOCK_QUERIES]
            q = np.zeros((BLOCK_QUERIES, self.dims), np.float32)
            q[: len(chunk)] = [s["vector"] for s in chunk]
            s, i = top(jnp.asarray(q), x, xx)
            s, i = np.asarray(s), np.asarray(i)
            out.extend((i[j].astype(np.int64), s[j])
                       for j in range(len(chunk)))
        return out
