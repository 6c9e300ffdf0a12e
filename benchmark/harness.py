"""The parts of a run that no configuration or traffic mix owns.

- :class:`Served`: one node booted as ``rest.server.run_single_node``
  boots it, with its HTTP server on an event-loop thread of this
  process, and a keep-alive client for set-up and stats;
- :func:`install_shards`: array-built segments installed into every
  shard through the snapshot-restore entry of the shard's engine;
- :func:`pack_mesh_plane`: where the index has several shards, the mesh
  plane packed and serving before the window;
- :func:`warm_up`: every (batch size, work bucket) the cell's traffic
  can drain, sent as ``_msearch`` batches before the window;
- :func:`compare`: the numbers that decide ``correct``, from the
  window's own responses against the configuration's numpy reference.
"""

from __future__ import annotations

import asyncio
import http.client
import json
import math
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

K = 10


class Served:
    """One node on the threaded scheduler, its HTTP server on 127.0.0.1
    on an event-loop thread of this process."""

    def __init__(self):
        from elasticsearch_tpu.rest.server import HttpServer, boot_single_node
        self.node = boot_single_node()
        self.server = HttpServer(self.node.client, "127.0.0.1", 0)
        self.loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.server.start())
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, name="http", daemon=True)
        self.thread.start()
        if not started.wait(60):
            raise RuntimeError("HTTP server did not start")
        self.port = self.server.port
        self.conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                               timeout=1800)

    def request(self, method: str, path: str, body=None,
                ndjson: bool = False):
        if body is not None and not isinstance(body, (bytes, str)):
            body = json.dumps(body)
        ctype = "application/x-ndjson" if ndjson else "application/json"
        self.conn.request(method, path, body=body,
                          headers={"content-type": ctype})
        resp = self.conn.getresponse()
        raw = resp.read()
        return resp.status, (json.loads(raw) if raw else None)

    def ok(self, method: str, path: str, body=None, ndjson: bool = False):
        status, out = self.request(method, path, body, ndjson)
        if status != 200:
            raise RuntimeError(f"{method} {path} -> {status}: "
                               f"{str(out)[:500]}")
        return out

    def stats(self) -> dict:
        nodes = self.ok("GET", "/_nodes/stats")["nodes"]
        return next(iter(nodes.values()))

    def on_scheduler(self, fn: Callable[[], object], timeout: float = 600):
        """Run ``fn`` on the node's scheduler thread (where every
        node-internal call runs) and return its result."""
        box: dict = {}
        done = threading.Event()

        def run():
            try:
                box["out"] = fn()
            except BaseException as e:   # re-raised on the caller's thread
                box["err"] = e
            finally:
                done.set()

        self.node.scheduler.submit(run)
        if not done.wait(timeout):
            raise RuntimeError("scheduler task did not finish")
        if "err" in box:
            raise box["err"]
        return box.get("out")

    def close(self) -> None:
        self.conn.close()
        try:
            asyncio.run_coroutine_threadsafe(
                self.server.stop(), self.loop).result(30)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(30)
            self.node.stop()


def create_index(srv: Served, index: str, body: dict) -> None:
    srv.ok("PUT", f"/{index}", body)
    srv.ok("GET", f"/_cluster/health/{index}?wait_for_status=green"
                  f"&timeout=60s")


def install_segments(srv: Served, index: str, build_segments,
                     shard: int = 0) -> List[int]:
    """Install the segments ``build_segments(shard_label)`` returns into
    shard ``shard`` of ``index`` through ``InternalEngine.restore_segments``
    (the snapshot-restore path), then refresh. Returns the segment sizes
    of the index."""
    engine = srv.node.indices_service.shard(index, shard).engine
    segments = build_segments(engine.shard_label)

    def restore():
        engine.restore_segments(segments)
        engine.refresh()

    srv.on_scheduler(restore)
    cat = srv.ok("GET", "/_cat/segments?format=json")
    return [int(r["docs.count"]) for r in cat if r["index"] == index]


@dataclass(frozen=True)
class Layout:
    """How a configuration lays its index out, with one-shard defaults: a
    configuration of several shards sets ``n_shards``, offers
    ``build_shard(shard, shard_label)`` and names its docs in an
    ``id_space`` of its own (routed ``_id``s); ``warm_class`` gives the
    class of a query that a drain's shapes also follow."""
    n_shards: int
    id_space: int
    warm_class: Optional[Callable[[dict], int]]


def layout(data) -> Layout:
    return Layout(int(getattr(data, "n_shards", 1)),
                  int(getattr(data, "id_space", data.n_docs)),
                  getattr(data, "warm_class", None))


def install_shards(srv: Served, index: str, data) -> List[int]:
    """Install every shard of ``index``: one shard through
    ``data.build_segments(shard_label)``, several through
    ``data.build_shard(shard, shard_label)``."""
    n_shards = layout(data).n_shards
    if n_shards == 1:
        return install_segments(srv, index, data.build_segments)
    sizes: List[int] = []
    for shard in range(n_shards):
        sizes = install_segments(
            srv, index, lambda label, s=shard: data.build_shard(s, label),
            shard)
    return sizes


def mesh_counters(stats: dict) -> Dict[str, int]:
    """The mesh executor's and mesh planes' counters of one
    ``_nodes/stats`` snapshot (``mesh_plane``)."""
    m = stats.get("mesh_plane") or {}
    return {k: int(m.get(k) or 0) for k in
            ("mesh_searches", "mesh_batches", "mesh_fallbacks",
             "mesh_planes_resident")}


def pack_mesh_plane(srv: Served, index: str, body: str) -> dict:
    """Send one search, which packs the mesh plane on the node before it
    is answered, and check that a plane is resident and served it: the
    packing then counts in set-up, not in the window. Returns the
    ``mesh_plane`` section."""
    before = mesh_counters(srv.stats())
    srv.ok("POST", f"/{index}/_search", body)
    stats = srv.stats()
    now = mesh_counters(stats)
    if now["mesh_planes_resident"] == 0 or \
            now["mesh_searches"] == before["mesh_searches"]:
        raise RuntimeError(f"the mesh did not serve the index: "
                           f"{str(stats.get('mesh_plane'))[:500]}")
    return stats["mesh_plane"]


def new_segment(name: str, lo: int, hi: int, sources: list):
    """A segment of docs ``[lo, hi)`` with the bookkeeping ``_bulk``
    would leave: ``_id`` the doc's number, seqno in arrival order,
    version 1, primary term 1, every doc live. Fields are added by the
    configuration."""
    from elasticsearch_tpu.index.segment import Segment
    seg = Segment(name, hi - lo)
    seg.ids = [str(i) for i in range(lo, hi)]
    seg.id_to_doc = {d: i for i, d in enumerate(seg.ids)}
    seg.routings = [None] * (hi - lo)
    seg.sources = sources
    seg.seqnos = np.arange(lo, hi, dtype=np.int64)
    seg.versions = np.ones(hi - lo, np.int64)
    seg.primary_terms = np.ones(hi - lo, np.int64)
    return seg


def warm_ladder(keys: Sequence[float], sizes: Sequence[int],
                per_octave: int = 2, trials: int = 4000,
                classes: Optional[Sequence[int]] = None
                ) -> List[List[int]]:
    """Pool indices of the warm-up batches.

    A drain pads its query count to a power of two and its summed work
    (``keys``: the posting blocks each query reads) to a bucket. For
    each power-of-two size ``b`` in ``sizes``, seeded random drains of
    ``b // 2 + 1`` and of ``b`` queries from the pool show which levels
    of summed work (``per_octave`` to an octave) such drains reach; for
    each level one batch of distinct queries whose keys sum to about it
    is sent. Every (size, bucket) pair the traffic can drain is then met
    before the window.

    ``classes``, where a drain's shapes also follow the largest of its
    queries' classes (the term ranges a query's rows hold), makes each
    level one per (class, level) pair the random drains reach, built
    from queries of that class or below with one of that class."""
    w = np.asarray(keys, np.float64)
    cls = np.zeros(len(w), np.int64) if classes is None else \
        np.asarray(classes, np.int64)
    order = np.argsort(w, kind="stable")
    ws = w[order]
    rng = np.random.default_rng(0)
    out = []
    for b in sizes:
        levels = set()
        for m in sorted({b // 2 + 1, b}):
            m = min(m, len(w))
            picks = rng.integers(0, len(w), (trials, m))
            sums = np.concatenate([w[picks].sum(axis=1),
                                   [ws[:m].sum(), ws[-m:].sum()]])
            tops = np.concatenate([cls[picks].max(axis=1),
                                   [cls[order[:m]].max(),
                                    cls[order[-m:]].max()]])
            levels |= {(m, int(c), int(v)) for c, v in zip(
                tops, np.floor(per_octave * np.log2(np.maximum(sums, 1))))}
        for m, c, level in sorted(levels):
            sub = order[cls[order] <= c]
            each = 2.0 ** ((level + 0.5) / per_octave) / m
            at = int(np.clip(np.searchsorted(w[sub], each) - m // 2, 0,
                             len(sub) - m))
            batch = [int(i) for i in sub[at: at + m]]
            if cls[batch].max() < c:
                top = np.flatnonzero(cls == c)
                batch[m // 2] = int(top[np.argmin(
                    np.abs(w[top] - w[batch[m // 2]]))])
            out.append(batch)
    return out


def warm_up(srv: Served, index: str, bodies: Sequence[str],
            batches: Sequence[List[int]]) -> int:
    """Send each batch as one ``_msearch`` (its lines land in one drain);
    returns the number of queries sent. Any failure is fatal: a shape
    that fails here would fail in the window."""
    sent = 0
    for batch in batches:
        lines = "".join('{"index":"%s"}\n%s\n' % (index, bodies[i])
                        for i in batch)
        out = srv.ok("POST", "/_msearch", lines, ndjson=True)
        for r in out["responses"]:
            if "error" in r or r.get("_shards", {}).get("failed"):
                raise RuntimeError(f"warm-up query failed: {str(r)[:500]}")
        sent += len(batch)
    return sent


# ---------------------------------------------------------------------------
# statistics of the window
# ---------------------------------------------------------------------------

def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (p in [0, 100]); inf counts as slowest."""
    s = sorted(values)
    if not s:
        return math.inf
    rank = max(1, math.ceil(p / 100.0 * len(s)))
    return s[rank - 1]


def class_entry(stats: dict, key: str) -> dict:
    return (stats.get("search_latency", {}).get("classes", {})
            .get(key) or {})


def span_delta(before: dict, after: dict, key: str, span: str):
    """(count, sum in ms) of ``span`` under the ``class|plane`` key
    between two ``_nodes/stats`` snapshots; the exact count and the
    mean carry the sum."""
    def total(stats):
        e = class_entry(stats, key)
        h = e.get("latency") if span == "total" else \
            (e.get("spans") or {}).get(span)
        h = h or {}
        return int(h.get("count") or 0), \
            float(h.get("mean_ms") or 0.0) * int(h.get("count") or 0)
    c0, s0 = total(before)
    c1, s1 = total(after)
    return c1 - c0, s1 - s0


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

class DenseScores:
    """A query's reference score of every doc, 0 where it does not
    match."""

    def __init__(self, scores: np.ndarray):
        self.scores = scores

    def top(self, k: int) -> np.ndarray:
        s = self.scores
        if len(s) > k:
            s = s[np.argpartition(-s, k - 1)[:k]]
        return np.sort(s)[::-1]

    def score(self, doc: int) -> float:
        return float(self.scores[doc])


def compare(responses: Sequence[Optional[dict]], reference: Callable,
            want_source: Callable[[int], Optional[dict]], n_docs: int,
            k: int = K) -> Dict[str, float]:
    """The numbers compared with their limits, over checked responses.

    ``reference(i)`` gives response i's reference scores as an object
    with ``top(k)`` (the k best scores, descending) and ``score(doc)``
    (0 where the doc does not match); ``want_source(doc)`` gives the
    ``_source`` the doc was indexed with, or None when the request asked
    for none.

    - ``rank_gap``: the widest gap, over ranks, between the reference's
      i-th best score and the reference score of the doc the engine put
      at rank i, over the reference's best score. A hit missing from a
      full list counts its reference score as the gap; a hit that does
      not match, or is listed twice, counts 1.
    - ``score_gap``: the widest gap between a hit's served score and its
      reference score, over the reference's best score.
    - ``fetch_errors``: hits whose ``_id`` or ``_source`` is not the
      doc's own.
    - ``lost``: checked requests that got no 200 answer.
    """
    rank_gap = score_gap = 0.0
    fetch_errors = lost = 0
    for i, resp in enumerate(responses):
        if resp is None:
            lost += 1
            continue
        ref = reference(i)
        want = ref.top(k)
        want = want[want > 0]
        top = float(want[0]) if len(want) else 1.0
        hits = resp["hits"]["hits"]
        seen = set()
        for rank in range(max(len(hits), len(want))):
            if rank >= len(hits):
                rank_gap = max(rank_gap, float(want[rank]) / top)
                continue
            hit = hits[rank]
            try:
                doc = int(hit["_id"])
            except (KeyError, TypeError, ValueError):
                fetch_errors += 1
                rank_gap = max(rank_gap, 1.0)
                continue
            got = ref.score(doc) if 0 <= doc < n_docs else 0.0
            if doc in seen or got <= 0:
                rank_gap = max(rank_gap, 1.0)
                continue
            seen.add(doc)
            if rank < len(want):
                rank_gap = max(rank_gap, (float(want[rank]) - got) / top)
            score_gap = max(score_gap,
                            abs(float(hit["_score"]) - got) / top)
            src = want_source(doc)
            if src is None:
                if "_source" in hit:
                    fetch_errors += 1
            elif hit.get("_source") != src:
                fetch_errors += 1
    return {"rank_gap": float(rank_gap), "score_gap": float(score_gap),
            "fetch_errors": fetch_errors, "lost": lost}
