"""Exact kNN: each drain reads the float32 matrix and its norms once
(N x D x 4 + N x 4 bytes); each query takes 2 x N x D operations."""

from benchmark.readers import batch_delta

PREFIXES = ("knn",)


def work(w):
    n, d = w.data.n_docs, w.data.dims
    drains = batch_delta(w, "batches_dispatched")
    return (2.0 * len(w.sent) * n * d,
            float(drains * (n * d * 4 + n * 4)))
