"""What a per-layer metric reader is handed, and the reductions they share.

Each ``metrics/<name>.py`` defines ``read(w: Window)`` and returns a
number, or None where the window holds nothing for it to read (the
harness then leaves the metric out of the line).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

from benchmark import harness, trace_reduce


@dataclass
class Window:
    traffic: dict
    query_class: str                 # bm25 | knn
    seconds: float
    latencies_ms: List[float]        # every request of the window
    late_ms: List[float]             # sent - due, open loop
    stats_before: dict               # _nodes/stats at the window's open
    stats_after: dict                # ... and at its close
    data: object                     # the configuration's Data
    sent: list                       # pool specs of the window's requests
    peaks: dict                      # peaks.json entry of this device
    trace: Optional[dict] = None     # trace_reduce.load() of the window
    window_ns: float = 0.0
    extra: dict = field(default_factory=dict)


def shard_key(w: Window) -> str:
    return f"{w.query_class}|batch"


def coordinator_key(w: Window) -> str:
    return f"{w.query_class}|fanout"


def mesh_key(w: Window) -> str:
    return f"{w.query_class}|mesh"


def mesh_delta(w: Window, name: str) -> int:
    return harness.mesh_counters(w.stats_after)[name] - \
        harness.mesh_counters(w.stats_before)[name]


def span_mean_ms(w: Window, key: str, span: str) -> Optional[float]:
    n, total_ms = harness.span_delta(w.stats_before, w.stats_after, key,
                                     span)
    return total_ms / n if n > 0 else None


def batch_delta(w: Window, name: str) -> int:
    return int(w.stats_after.get("search_batch", {}).get(name, 0)) - \
        int(w.stats_before.get("search_batch", {}).get(name, 0))


def compiles_delta(w: Window) -> int:
    def total(s):
        return int(s.get("device_profile", {}).get("total_compiles", 0))
    return total(w.stats_after) - total(w.stats_before)


def mean(values: List[float]) -> Optional[float]:
    finite = [v for v in values if math.isfinite(v)]
    return sum(finite) / len(finite) if finite else None


def idle_pct(w: Window) -> Optional[float]:
    if w.trace is None or not w.trace["devices"] or w.window_ns <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_ns(w.trace, w.window_ns)
                    / w.window_ns)


def roofline_pct(w: Window, family, chips: int = 1) -> Optional[float]:
    """100 x least time / device time of ``family``'s modules: the least
    time is the larger of its operations over the peak FLOP/s and its
    bytes over the peak bytes/s, for the work ``family.work`` says the
    window's queries need, spread over ``chips`` chips; the device time
    is the mean over the traced chips."""
    if w.trace is None:
        return None
    t_ns = trace_reduce.family_ns(w.trace, family.PREFIXES)
    if t_ns <= 0:
        return None
    flops, nbytes = family.work(w)
    least_s = max(flops / w.peaks["flops_per_s"],
                  nbytes / w.peaks["hbm_bytes_per_s"]) / chips
    return 100.0 * least_s / (t_ns / 1e9)
