"""Device mesh construction + the mesh-sharded plane's SPMD kernels.

The data-plane analog of the reference's node topology: an index's shards map
onto the ``shard`` mesh axis (each device slice holds a doc partition, like
an ES shard on a data node), while the ``dp`` axis replicates the corpus for
query-batch throughput (like ES replicas serving reads,
README.asciidoc:13). Collectives ride ICI inside the mesh — the data-plane
half of the two-plane split (SURVEY.md §5.8 TPU-native equivalent).

The second half of this module is the serving tier's kernel factories
(ROADMAP item 2): shard_map programs over the **mesh-sharded plane**
(ops/device_segment.py MeshPlaneRegistry) — each co-located ES shard's
packed plane occupies one slot of a ``[S, ...]`` stack laid out with
``NamedSharding`` over the ``shard`` mesh axis (model parallel), the
micro-batched query stack rides ``dp``, and ONE compiled program scores
every (shard, query) pair with each slot's arithmetic identical to the
single-shard plane kernels (ops/bm25.py `_bm25_flat_kernel`,
ops/knn.py `_batch_scores`, ops/sparse.py) so mesh residency can never
change a served result. Per-shard top-k comes back stitched along the
shard axis; the host-side demux and coordinator merge stay unchanged.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.search.device_profile import profiled_callable


def make_mesh(n_shards: Optional[int] = None, n_dp: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Create a (dp, shard) mesh. Defaults to all devices on the shard axis."""
    devices = list(devices if devices is not None else jax.devices())
    if n_shards is None:
        n_shards = len(devices) // n_dp
    if n_dp * n_shards != len(devices):
        raise ValueError(
            f"dp({n_dp}) x shard({n_shards}) != device count {len(devices)}")
    arr = np.asarray(devices).reshape(n_dp, n_shards)
    return Mesh(arr, ("dp", "shard"))


def shard_spec(mesh: Mesh, *axes: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# serving-tier mesh layout (mesh-sharded plane)
# ---------------------------------------------------------------------------

_MESH_CACHE: Dict[Tuple, Mesh] = {}


# ---------------------------------------------------------------------------
# multi-host topology (search.mesh.hosts)
# ---------------------------------------------------------------------------

class HostTopology:
    """N hosts x M devices per host — the ``num_nodes`` /
    ``gpus_per_node`` shape multi-process SPMD deployments pin
    explicitly. Hosts partition the device axis CONTIGUOUSLY (device d
    lives on host d // devices_per_host), the standard process-major
    device order of multi-process jax, so a ``(dp, shard)`` mesh over
    the first ``dp*d_used`` devices spans hosts 0..ceil(dp*d_used/M)-1
    and each plane slot has a well-defined serving host."""

    __slots__ = ("n_hosts", "devices_per_host", "spec")

    def __init__(self, n_hosts: int, devices_per_host: int,
                 spec: str = ""):
        self.n_hosts = int(n_hosts)
        self.devices_per_host = int(devices_per_host)
        self.spec = spec or f"{n_hosts}x{devices_per_host}"

    @property
    def n_devices(self) -> int:
        return self.n_hosts * self.devices_per_host

    def host_of_device(self, device_index: int) -> int:
        return min(device_index // self.devices_per_host,
                   self.n_hosts - 1)

    def __eq__(self, other) -> bool:
        return (isinstance(other, HostTopology)
                and self.n_hosts == other.n_hosts
                and self.devices_per_host == other.devices_per_host)

    def __hash__(self) -> int:
        return hash((self.n_hosts, self.devices_per_host))

    def __repr__(self) -> str:
        return (f"HostTopology({self.n_hosts}x{self.devices_per_host},"
                f" spec={self.spec!r})")


def parse_host_topology(spec: str, total: Optional[int] = None
                        ) -> Optional[HostTopology]:
    """``search.mesh.hosts`` -> HostTopology. "" = single-host (None);
    "N" = N equal hosts over the visible devices; "NxM" = N hosts x M
    devices per host. Raises ValueError when the spec asks for more
    devices than the backend exposes — a misdeclared fleet must fail
    loudly at configure time, not mis-shard at dispatch time."""
    spec = (spec or "").strip()
    if not spec:
        return None
    if total is None:
        total = len(jax.devices())
    if "x" in spec:
        hosts_s, _, per_s = spec.partition("x")
        n_hosts, per = int(hosts_s), int(per_s)
    else:
        n_hosts, per = int(spec), 0
    if n_hosts < 1:
        raise ValueError(
            f"search.mesh.hosts [{spec}]: host count must be >= 1")
    if per == 0:
        if n_hosts > total:
            raise ValueError(
                f"search.mesh.hosts [{spec}]: {n_hosts} hosts over "
                f"{total} visible devices")
        per = total // n_hosts
    if per < 1 or n_hosts * per > total:
        raise ValueError(
            f"search.mesh.hosts [{spec}]: {n_hosts}x{per} devices "
            f"exceed the {total} visible")
    return HostTopology(n_hosts, per, spec)


def mesh_member_hosts(topo: HostTopology, dp: int, d_used: int
                      ) -> Tuple[int, ...]:
    """Hosts whose devices participate in a (dp, d_used) mesh — the
    membership the executor's liveness checks (and the typed
    ``mesh_host_lost`` fallback) are defined over."""
    return tuple(sorted({topo.host_of_device(d)
                         for d in range(dp * d_used)}))


def slot_host(topo: HostTopology, slot: int, slots_per_device: int,
              ) -> int:
    """Primary host serving a plane slot: slots partition contiguously
    over the shard-axis device columns, and dp row 0 of column j is
    global device j."""
    return topo.host_of_device(slot // max(1, slots_per_device))


# The process's host-partition backend: maps cluster nodes onto virtual
# (or real) mesh hosts and answers liveness. Duck-typed protocol —
# ``topology`` (HostTopology), ``host_of_node(node_id)``,
# ``host_alive(host)``, ``nodes_on_host(host)``, ``indices_of(node_id)``
# (the member's IndicesService, for the single-process stand-in where
# one process holds every host's devices), ``pressure_snapshot(node_id)``.
# testing.VirtualHostBackend registers here; a real multi-process
# runtime would install its own.
_HOST_BACKEND = None


def set_host_backend(backend) -> None:
    global _HOST_BACKEND
    _HOST_BACKEND = backend


def host_backend():
    return _HOST_BACKEND


def mesh_layout(n_shards: int, dp: int = 1, max_devices: int = 0,
                hosts: Optional[HostTopology] = None
                ) -> Tuple[Mesh, int, int]:
    """(mesh, n_slots, slots_per_device) for ``n_shards`` mesh-served
    shards over the fleet's devices.

    One shard = one slot of the stacked plane; slots map onto a
    ``(dp, shard)`` mesh over a device SUBSET sized to the shard count
    (2 shards on an 8-chip host use 2 chips — the other 6 stay free for
    other planes), padding the slot count up to a multiple of the used
    devices when shards outnumber chips. ``max_devices`` (0 = all)
    bounds the subset — the single-device layout is the byte-identity
    baseline the golden tests pin. ``hosts`` (search.mesh.hosts) caps
    the subset at the declared fleet and makes the device order
    host-contiguous by construction, so growing the shard count walks
    the program onto additional HOSTS, not just additional chips."""
    devices = jax.devices()
    total = len(devices)
    if hosts is not None:
        total = min(total, hosts.n_devices)
    if max_devices > 0:
        total = min(total, max_devices)
    dp = max(1, min(int(dp), total))
    d_used = max(1, min(total // dp, n_shards))
    n_slots = -(-n_shards // d_used) * d_used
    key = (dp, d_used)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        arr = np.asarray(devices[: dp * d_used]).reshape(dp, d_used)
        mesh = Mesh(arr, ("dp", "shard"))
        _MESH_CACHE[key] = mesh
    return mesh, n_slots, n_slots // d_used


# ---------------------------------------------------------------------------
# mesh-sharded plane kernels (one slot = one ES shard's packed plane)
# ---------------------------------------------------------------------------

_COMPILED: Dict[Tuple, object] = {}


def mesh_bm25_flat(mesh: Mesh, n_docs_pad: int, n_q: int, k: int,
                   n_segs: int, k1: float, b: float):
    """One SPMD program over the stacked postings planes.

    fn(block_docs [S,NB,B], block_tfs [S,NB,B], doc_lens [S,N],
       flat_idx [S,DP,FB], flat_w [S,DP,FB], flat_q [S,DP,FB],
       flat_avgdl [S,DP,FB], live [S,N], seg_ids [S,N])
      -> (scores [S,DP,n_q,k], plane docs [S,DP,n_q,k],
          hits [S,DP,n_q,n_segs])

    The flat gather stacks SPLIT over the dp axis: each dp row holds
    its own ``n_q``-query slice of the fan-out's micro-batch (the
    corpus stack stays replicated per row), so added dp rows buy query
    throughput instead of re-scoring the identical stack. Each
    (slot, row) runs exactly ops/bm25.py ``bm25_flat_body`` — the SAME
    traced function `_bm25_flat_kernel` / `_bm25_flat_kernel_seg` call
    (same gather/scatter order, same f32 adds), so every query's row is
    bit-compatible with that shard's single-plane dispatch BY
    CONSTRUCTION, at any dp. Per-segment hit counts serve BOTH totals
    contracts host-side: summed for counts-then-skip, clipped per
    segment for totals-disabled."""
    from elasticsearch_tpu.ops.bm25 import bm25_flat_body
    key = ("bm25", id(mesh), n_docs_pad, n_q, k, n_segs, k1, b)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def one_slot(bd, bt, dl, fi, fw, fq, fa, lv, si):
        # fi/fw/fq/fa: [1, FB] — this device's dp row of the stack
        scores, matched = bm25_flat_body(bd, bt, fi[0], fw[0], fq[0],
                                         dl, fa[0], lv, n_docs_pad,
                                         n_q, k1=k1, b=b)
        s, d = jax.lax.top_k(scores, k)
        onehot = jax.nn.one_hot(si, n_segs, dtype=jnp.int32)
        hits = matched.astype(jnp.int32) @ onehot
        return s[None], d[None], hits[None]

    def local(bd, bt, dl, fi, fw, fq, fa, lv, si):
        return jax.vmap(one_slot)(bd, bt, dl, fi, fw, fq, fa, lv, si)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    pq = P("shard", "dp", None)
    pout = P("shard", "dp", None, None)
    fn = profiled_callable("mesh_bm25_flat", shard_map(
        local, mesh=mesh,
        in_specs=(p3, p3, p2, pq, pq, pq, pq, p2, p2),
        out_specs=(pout, pout, pout), check_vma=False))
    _COMPILED[key] = fn
    return fn


def mesh_bm25_coarse(mesh: Mesh, n_docs_pad: int, n_q: int, kprime: int,
                     n_segs: int, k1: float, b: float):
    """Quantized coarse tier over the stacked postings planes: one SPMD
    program whose per-slot body is EXACTLY ops/bm25.py
    ``bm25_coarse_body`` (bf16 mirror gathers, f32 accumulation), so a
    slot's coarse candidates match that shard's single-plane coarse
    dispatch by construction.

    fn(block_docs [S,NB,B], block_tfs_q [S,NB,B] bf16, doc_lens_q [S,N]
       bf16, flat_idx [S,FB], flat_w [S,FB], flat_q [S,FB],
       flat_avgdl [S,FB], live [S,N], seg_ids [S,N])
      -> (coarse scores [S,n_q,k'], cand [S,n_q,k'],
          hits [S,n_q,n_segs])"""
    from elasticsearch_tpu.ops.bm25 import bm25_coarse_body
    key = ("bm25_coarse", id(mesh), n_docs_pad, n_q, kprime, n_segs,
           k1, b)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def one_slot(bd, btq, dlq, fi, fw, fq, fa, lv, si):
        return bm25_coarse_body(bd, btq, fi, fw, fq, dlq, fa, lv, si,
                                n_docs_pad, n_q, n_segs, kprime,
                                k1=k1, b=b)

    def local(bd, btq, dlq, fi, fw, fq, fa, lv, si):
        return jax.vmap(one_slot)(bd, btq, dlq, fi, fw, fq, fa, lv, si)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    fn = profiled_callable("mesh_bm25_coarse", shard_map(
        local, mesh=mesh,
        in_specs=(p3, p3, p2, p2, p2, p2, p2, p2, p2),
        out_specs=(p3, p3, p3), check_vma=False))
    _COMPILED[key] = fn
    return fn


def mesh_bm25_rerank(mesh: Mesh, k: int, k1: float, b: float):
    """Exact re-rank tier over the stacked postings planes: per-slot
    body is ops/bm25.py ``bm25_rerank_body`` — each candidate looked up
    in its query's term ranges of that slot's plane, the exact flat
    kernel's f32 contribution summed in flat-plan order — so re-ranked
    scores are bit-compatible with the per-shard quantized path AND the
    exact path.

    fn(block_docs, block_tfs [S,NB,B] f32, r_start, r_count, r_w,
       r_avgdl [S,n_q,R], doc_lens [S,N] f32, live [S,N],
       cand [S,n_q,k'], coarse_s [S,n_q,k'])
      -> (scores [S,n_q,k], plane docs [S,n_q,k], eps [S,n_q])"""
    from elasticsearch_tpu.ops.bm25 import bm25_rerank_body
    key = ("bm25_rerank", id(mesh), k, k1, b)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def one_slot(bd, bt, rs, rc, rw, ra, dl, lv, cand, cs):
        return bm25_rerank_body(bd, bt, rs, rc, rw, ra, dl, lv, cand, cs,
                                k, k1=k1, b=b)

    def local(bd, bt, rs, rc, rw, ra, dl, lv, cand, cs):
        return jax.vmap(one_slot)(bd, bt, rs, rc, rw, ra, dl, lv, cand,
                                  cs)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    fn = profiled_callable("mesh_bm25_rerank", shard_map(
        local, mesh=mesh,
        in_specs=(p3, p3, p3, p3, p3, p3, p2, p2, p3, p3),
        out_specs=(p3, p3, p2), check_vma=False))
    _COMPILED[key] = fn
    return fn


def mesh_sparse_coarse(mesh: Mesh, n_docs_pad: int, kprime: int):
    """Quantized coarse tier over the stacked rank_features planes;
    per-slot body is ops/sparse.py ``sparse_coarse_body``.

    fn(block_docs [S,NB,B], block_weights_q [S,NB,B] bf16, idx [S,Q,QB],
       qw [S,Q,QB], live [S,N])
      -> (coarse scores [S,Q,k'], cand [S,Q,k'], hits [S,Q])"""
    from elasticsearch_tpu.ops.sparse import sparse_coarse_body
    key = ("sparse_coarse", id(mesh), n_docs_pad, kprime)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def one_slot(bd, bwq, bi, qw, lv):
        return sparse_coarse_body(bd, bwq, bi, qw, lv, n_docs_pad,
                                  kprime)

    def local(bd, bwq, bi, qw, lv):
        return jax.vmap(one_slot)(bd, bwq, bi, qw, lv)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    fn = profiled_callable("mesh_sparse_coarse", shard_map(
        local, mesh=mesh,
        in_specs=(p3, p3, p3, p3, p2),
        out_specs=(p3, p3, p2), check_vma=False))
    _COMPILED[key] = fn
    return fn


def mesh_sparse_rerank(mesh: Mesh, n_docs_pad: int, kprime: int, k: int):
    """Exact re-rank tier over the stacked rank_features planes;
    per-slot body is ops/sparse.py ``sparse_rerank_body``.

    fn(block_docs, block_weights [S,NB,B] f32, idx [S,Q,QB],
       qw [S,Q,QB], live [S,N], cand [S,Q,k'], coarse_s [S,Q,k'])
      -> (scores [S,Q,k], plane docs [S,Q,k], eps [S,Q])"""
    from elasticsearch_tpu.ops.sparse import sparse_rerank_body
    key = ("sparse_rerank", id(mesh), n_docs_pad, kprime, k)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def one_slot(bd, bw, bi, qw, lv, cand, cs):
        return sparse_rerank_body(bd, bw, bi, qw, lv, cand, cs,
                                  n_docs_pad, kprime, k)

    def local(bd, bw, bi, qw, lv, cand, cs):
        return jax.vmap(one_slot)(bd, bw, bi, qw, lv, cand, cs)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    fn = profiled_callable("mesh_sparse_rerank", shard_map(
        local, mesh=mesh,
        in_specs=(p3, p3, p3, p3, p2, p3, p3),
        out_specs=(p3, p3, p2), check_vma=False))
    _COMPILED[key] = fn
    return fn


def mesh_knn_coarse(mesh: Mesh, kprime: int, similarity: str,
                    masked: bool):
    """Quantized int8 coarse tier over the stacked vector planes: the
    query stack rides ``dp``, the corpus the ``shard`` axis, and each
    slot runs ops/knn.py's ``_coarse_plane`` arithmetic (int8 x int8
    MXU matmul, int32 accumulate, rescale + positive-score transform).

    fn(q8 [S,N,D] int8, scales [S,N], norms [S,N], allowed [S,N],
       queries [Q,D] [, masks [S,Q,N]])
      -> (coarse scores [S,Q,k'], cand [S,Q,k'])"""
    from elasticsearch_tpu.ops.knn import _coarse_plane
    key = ("knn_coarse", id(mesh), kprime, similarity, masked)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def local(q8, sc, nr, al, q, mk=None):
        def one_slot(q8_s, sc_s, nr_s, al_s, mk_s=None):
            s = _coarse_plane(q8_s, sc_s, nr_s, q, similarity)
            ok = al_s[None, :] if mk_s is None else (al_s[None, :] & mk_s)
            s = jnp.where(ok, s, -jnp.inf)
            cs, cand = jax.lax.top_k(s, kprime)
            return cs, cand
        if mk is not None:
            return jax.vmap(one_slot)(q8, sc, nr, al, mk)
        return jax.vmap(lambda a, b_, c, d: one_slot(a, b_, c, d))(
            q8, sc, nr, al)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    pq = P("dp", None)
    pout = P("shard", "dp", None)
    if masked:
        fn = profiled_callable("mesh_knn_coarse", shard_map(
            local, mesh=mesh,
            in_specs=(p3, p2, p2, p2, pq, P("shard", "dp", None)),
            out_specs=(pout, pout), check_vma=False))
    else:
        fn = profiled_callable("mesh_knn_coarse", shard_map(
            lambda q8, sc, nr, al, q: local(q8, sc, nr, al, q),
            mesh=mesh, in_specs=(p3, p2, p2, p2, pq),
            out_specs=(pout, pout), check_vma=False))
    _COMPILED[key] = fn
    return fn


def mesh_knn_rerank(mesh: Mesh, k: int, similarity: str, masked: bool):
    """Exact re-rank tier over the stacked vector planes; per-slot body
    is ops/knn.py ``knn_rerank_body`` (candidate sort, exact einsum
    scores, observed-deviation eps), so re-ranked scores match the
    per-shard quantized path bit-for-bit.

    fn(matrix [S,N,D] f32, norms [S,N], allowed [S,N], queries [Q,D],
       cand [S,Q,k'], coarse_s [S,Q,k'] [, masks [S,Q,N]])
      -> (scores [S,Q,k], plane docs [S,Q,k], eps [S,Q])"""
    from elasticsearch_tpu.ops.knn import knn_rerank_body
    key = ("knn_rerank", id(mesh), k, similarity, masked)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def local(m, nr, al, q, cand, cs, mk=None):
        def one_slot(m_s, nr_s, al_s, cand_s, cs_s, mk_s=None):
            return knn_rerank_body(m_s, nr_s, al_s, q, cand_s, cs_s,
                                   mk_s, k, similarity)
        if mk is not None:
            return jax.vmap(one_slot)(m, nr, al, cand, cs, mk)
        return jax.vmap(
            lambda a, b_, c, d, e: one_slot(a, b_, c, d, e))(
            m, nr, al, cand, cs)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    pq = P("dp", None)
    pc = P("shard", "dp", None)
    pout = P("shard", "dp", None)
    if masked:
        fn = profiled_callable("mesh_knn_rerank", shard_map(
            local, mesh=mesh,
            in_specs=(p3, p2, p2, pq, pc, pc, P("shard", "dp", None)),
            out_specs=(pout, pout, P("shard", "dp")), check_vma=False))
    else:
        fn = profiled_callable("mesh_knn_rerank", shard_map(
            lambda m, nr, al, q, cand, cs: local(m, nr, al, q, cand, cs),
            mesh=mesh, in_specs=(p3, p2, p2, pq, pc, pc),
            out_specs=(pout, pout, P("shard", "dp")), check_vma=False))
    _COMPILED[key] = fn
    return fn


def mesh_sparse_topk(mesh: Mesh, n_docs_pad: int, k: int):
    """One SPMD program over the stacked rank_features planes.

    fn(block_docs [S,NB,B], block_weights [S,NB,B], idx [S,DP,Q,QB],
       qw [S,DP,Q,QB], live [S,N])
      -> (scores [S,DP,Q,k], plane docs [S,DP,Q,k], hits [S,DP,Q])

    The query stack SPLITS over the dp axis (each row scores its own
    Q-query slice against its corpus replica). Per (slot, row, query)
    the body is ops/sparse.py ``sparse_topk_body`` with linear scoring
    — the SAME traced function ``sparse_topk_batch`` vmaps, so a mesh
    row is bit-compatible with the single-shard batch dispatch by
    construction: same gather, same scatter-add, exact whole-shard
    counts off the score plane."""
    from elasticsearch_tpu.ops.sparse import sparse_topk_body
    key = ("sparse", id(mesh), n_docs_pad, k)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def one_slot(bd, bw, bi, qw, lv):
        def one_q(bi_q, qw_q):
            return sparse_topk_body(bd, bw, bi_q, qw_q, 1.0, 1.0, lv,
                                    n_docs_pad, k, "linear")
        ts, td, hits = jax.vmap(one_q)(bi[0], qw[0])
        return ts[None], td[None], hits[None]

    def local(bd, bw, bi, qw, lv):
        return jax.vmap(one_slot)(bd, bw, bi, qw, lv)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    pq = P("shard", "dp", None, None)
    fn = profiled_callable("mesh_sparse_topk", shard_map(
        local, mesh=mesh,
        in_specs=(p3, p3, pq, pq, p2),
        out_specs=(pq, pq, P("shard", "dp", None)), check_vma=False))
    _COMPILED[key] = fn
    return fn


def mesh_knn_topk(mesh: Mesh, k: int, similarity: str, masked: bool):
    """One SPMD program over the stacked vector planes: the query stack
    rides the ``dp`` mesh axis, the corpus the ``shard`` axis.

    fn(matrix [S,N,D], norms [S,N], allowed [S,N], queries [Q,D]
       [, masks [S,Q,N]]) -> (scores [S,Q,k], plane docs [S,Q,k])

    Scoring is ops/knn.py ``knn_topk_body`` per slot — the SAME traced
    function `knn_topk_batch` / `knn_topk_batch_masked` call (bf16
    multiply, f32 accumulate, `_coarse_similarity` transform), so each
    slot's row matches that shard's exact plane matmul by construction.
    ``allowed`` already folds live & exists (& a shared filter mask when
    every batch member carries the same filter); ``masks`` is the
    per-member stack for heterogeneous filters."""
    from elasticsearch_tpu.ops.knn import knn_topk_body
    key = ("knn", id(mesh), k, similarity, masked)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn

    def local(m, nr, al, q, mk=None):
        def one_slot(m_s, nr_s, al_s, mk_s=None):
            return knn_topk_body(m_s, nr_s, al_s, q, mk_s, k, similarity)
        if mk is not None:
            return jax.vmap(one_slot)(m, nr, al, mk)
        return jax.vmap(lambda a, c, d: one_slot(a, c, d))(m, nr, al)

    p3 = P("shard", None, None)
    p2 = P("shard", None)
    pq = P("dp", None)
    pout = P("shard", "dp", None)
    if masked:
        fn = profiled_callable("mesh_knn_topk", shard_map(
            local, mesh=mesh,
            in_specs=(p3, p2, p2, pq, P("shard", "dp", None)),
            out_specs=(pout, pout), check_vma=False))
    else:
        fn = profiled_callable("mesh_knn_topk", shard_map(
            lambda m, nr, al, q: local(m, nr, al, q), mesh=mesh,
            in_specs=(p3, p2, p2, pq),
            out_specs=(pout, pout), check_vma=False))
    _COMPILED[key] = fn
    return fn
