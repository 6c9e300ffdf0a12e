"""The served path's device programs compile for a TPU v5e.

Each test lowers one main-path kernel with the TPU compiler at the widths
``chip_smoke.py`` serves (a 2^20-doc plane, 128-d vectors) for a chip that
is described, not attached — what the chip's compiler refuses fails here,
at no chip time. Nothing runs; a pass is not a chip run.

The ``v5e:2x2`` topology is described only inside the module-scoped
fixture below (never at import, in conftest, in a skipif or a parametrize
argument): only one process may load the TPU library, and the xdist
worker that is given this file keeps it until it exits. All compiles stay
in this one file for the same reason.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

N_DOCS = 1 << 20          # the one-chip plane (chip_smoke.py default)
N_SHARD = 1 << 18         # one of the four-chip phase's four shards
DIMS = 128
NB = 1 << 19              # postings / feature blocks of a 2^20-doc plane
BLOCK = 128
Q = 8                     # ops/knn.py MIN_QUERY_ROWS: the solo batch width
K = 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), ("dp", "shard"))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_bm25_flat_compiles(one_chip):
    from elasticsearch_tpu.ops.bm25 import _bm25_flat_kernel
    fb = 8192
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(_bm25_flat_kernel,
             s((NB, BLOCK), jnp.int32), s((NB, BLOCK), jnp.float32),
             s((fb,), jnp.int32), s((fb,), jnp.float32), s((fb,), jnp.int32),
             s((N_DOCS,), jnp.float32), s((fb,), jnp.float32),
             s((N_DOCS,), jnp.bool_),
             n_docs_pad=N_DOCS, n_q=Q, k=K, counted=True)


def test_bm25_coarse_and_rerank_compile(one_chip):
    from elasticsearch_tpu.ops.bm25 import (
        _bm25_coarse_kernel, _bm25_rerank_kernel,
    )
    fb, kprime, n_segs, r = 8192, 128, 4, 64
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(_bm25_coarse_kernel,
             s((NB, BLOCK), jnp.int32), s((NB, BLOCK), jnp.bfloat16),
             s((fb,), jnp.int32), s((fb,), jnp.float32), s((fb,), jnp.int32),
             s((N_DOCS,), jnp.bfloat16), s((fb,), jnp.float32),
             s((N_DOCS,), jnp.bool_), s((N_DOCS,), jnp.int32),
             n_docs_pad=N_DOCS, n_q=Q, n_segs=n_segs, kprime=kprime)
    _compile(_bm25_rerank_kernel,
             s((NB, BLOCK), jnp.int32), s((NB, BLOCK), jnp.float32),
             s((Q, r), jnp.int32), s((Q, r), jnp.int32),
             s((Q, r), jnp.float32), s((Q, r), jnp.float32),
             s((N_DOCS,), jnp.float32), s((N_DOCS,), jnp.bool_),
             s((Q, kprime), jnp.int32), s((Q, kprime), jnp.float32), k=K)


def test_bm25_coarse_with_dense_heads_compiles(one_chip):
    """The coarse kernel with the dense head mirror's columns and the
    tail's scatter branches."""
    from elasticsearch_tpu.ops.bm25 import DENSE_SLOTS, _bm25_coarse_kernel
    fb, kprime, n_segs, heads = 65536, 128, 4, 74
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(_bm25_coarse_kernel,
             s((NB, BLOCK), jnp.int32), s((NB, BLOCK), jnp.bfloat16),
             s((fb,), jnp.int32), s((fb,), jnp.float32), s((fb,), jnp.int32),
             s((N_DOCS,), jnp.bfloat16), s((fb,), jnp.float32),
             s((N_DOCS,), jnp.bool_), s((N_DOCS,), jnp.int32),
             n_docs_pad=N_DOCS, n_q=1, n_segs=n_segs, kprime=kprime,
             tail=s((), jnp.int32),
             dense=(s((heads + 1, N_DOCS), jnp.bfloat16),
                    s((1, DENSE_SLOTS), jnp.int32),
                    s((1, DENSE_SLOTS), jnp.float32),
                    s((n_segs,), jnp.float32)))


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_knn_topk_batch_compiles(one_chip, masked):
    from elasticsearch_tpu.ops.knn import (
        knn_topk_batch, knn_topk_batch_masked,
    )
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    args = [s((N_DOCS, DIMS), jnp.float32), s((N_DOCS,), jnp.float32),
            s((N_DOCS,), jnp.bool_), s((N_DOCS,), jnp.bool_),
            s((Q, DIMS), jnp.float32)]
    if masked:
        _compile(knn_topk_batch_masked, *args, s((Q, N_DOCS), jnp.bool_),
                 k=K, similarity="cosine")
    else:
        _compile(knn_topk_batch, *args, k=K, similarity="cosine")


def test_knn_coarse_and_rerank_compile(one_chip):
    from elasticsearch_tpu.ops.knn import (
        knn_coarse_candidates, knn_rerank_exact,
    )
    kprime = 128
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(knn_coarse_candidates,
             s((N_DOCS, DIMS), jnp.int8), s((N_DOCS,), jnp.float32),
             s((N_DOCS,), jnp.float32), s((N_DOCS,), jnp.bool_),
             s((Q, DIMS), jnp.float32), kprime=kprime, similarity="cosine")
    _compile(knn_rerank_exact,
             s((N_DOCS, DIMS), jnp.float32), s((N_DOCS,), jnp.float32),
             s((N_DOCS,), jnp.bool_), s((Q, DIMS), jnp.float32),
             s((Q, kprime), jnp.int32), s((Q, kprime), jnp.float32),
             k=K, similarity="cosine")


def test_sparse_topk_batch_compiles(one_chip):
    from elasticsearch_tpu.ops.sparse import sparse_topk_batch
    qb = 512
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(sparse_topk_batch,
             s((NB, BLOCK), jnp.int32), s((NB, BLOCK), jnp.float32),
             s((Q, qb), jnp.int32), s((Q, qb), jnp.float32),
             s((), jnp.float32), s((), jnp.float32), s((N_DOCS,), jnp.bool_),
             n_docs_pad=N_DOCS, k=K, function="linear", counted=True)


def test_rrf_fuse_batch_compiles(one_chip):
    from elasticsearch_tpu.ops.fusion import rrf_fuse_batch
    _compile(rrf_fuse_batch, _spec((Q, 2, 2 * K), jnp.int32, one_chip),
             n_docs_pad=1024, k=K, rank_constant=60)


def test_aggs_ordinal_counts_plane_compiles(one_chip):
    from elasticsearch_tpu.ops.aggs import ordinal_counts_plane
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(ordinal_counts_plane, s((N_DOCS,), jnp.int32),
             s((N_DOCS,), jnp.int32), s((Q, N_DOCS), jnp.bool_),
             n_buckets=64)


def test_mesh_bm25_flat_compiles_on_four_chips(mesh4):
    from elasticsearch_tpu.ops.bm25 import DEFAULT_B, DEFAULT_K1
    from elasticsearch_tpu.parallel.mesh import mesh_bm25_flat
    n_segs, fb, nb = 4, 4096, NB // 4
    fn = mesh_bm25_flat(mesh4, N_SHARD, Q, K, n_segs, DEFAULT_K1, DEFAULT_B)
    sh = lambda *axes: NamedSharding(mesh4, P(*axes))  # noqa: E731
    p3, p2, pq = sh("shard", None, None), sh("shard", None), \
        sh("shard", "dp", None)
    compiled = _compile(
        fn,
        _spec((4, nb, BLOCK), jnp.int32, p3),
        _spec((4, nb, BLOCK), jnp.float32, p3),
        _spec((4, N_SHARD), jnp.float32, p2),
        _spec((4, 1, fb), jnp.int32, pq), _spec((4, 1, fb), jnp.float32, pq),
        _spec((4, 1, fb), jnp.int32, pq), _spec((4, 1, fb), jnp.float32, pq),
        _spec((4, N_SHARD), jnp.bool_, p2),
        _spec((4, N_SHARD), jnp.int32, p2))
    assert len(compiled.output_shardings[0].device_set) == 4


def test_mesh_bm25_rerank_compiles_on_four_chips(mesh4):
    from elasticsearch_tpu.ops.bm25 import DEFAULT_B, DEFAULT_K1
    from elasticsearch_tpu.parallel.mesh import mesh_bm25_rerank
    kprime, r, nb = 128, 64, NB // 4
    fn = mesh_bm25_rerank(mesh4, K, DEFAULT_K1, DEFAULT_B)
    sh = lambda *axes: NamedSharding(mesh4, P(*axes))  # noqa: E731
    p3, p2 = sh("shard", None, None), sh("shard", None)
    compiled = _compile(
        fn,
        _spec((4, nb, BLOCK), jnp.int32, p3),
        _spec((4, nb, BLOCK), jnp.float32, p3),
        _spec((4, Q, r), jnp.int32, p3), _spec((4, Q, r), jnp.int32, p3),
        _spec((4, Q, r), jnp.float32, p3), _spec((4, Q, r), jnp.float32, p3),
        _spec((4, N_SHARD), jnp.float32, p2),
        _spec((4, N_SHARD), jnp.bool_, p2),
        _spec((4, Q, kprime), jnp.int32, p3),
        _spec((4, Q, kprime), jnp.float32, p3))
    assert len(compiled.output_shardings[0].device_set) == 4


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_mesh_knn_topk_compiles_on_four_chips(mesh4, masked):
    from elasticsearch_tpu.parallel.mesh import mesh_knn_topk
    fn = mesh_knn_topk(mesh4, K, "cosine", masked)
    sh = lambda *axes: NamedSharding(mesh4, P(*axes))  # noqa: E731
    args = [_spec((4, N_SHARD, DIMS), jnp.float32, sh("shard", None, None)),
            _spec((4, N_SHARD), jnp.float32, sh("shard", None)),
            _spec((4, N_SHARD), jnp.bool_, sh("shard", None)),
            _spec((Q, DIMS), jnp.float32, sh("dp", None))]
    if masked:
        args.append(_spec((4, Q, N_SHARD), jnp.bool_,
                          sh("shard", "dp", None)))
    compiled = _compile(fn, *args)
    assert len(compiled.output_shardings[0].device_set) == 4
