"""What the program's own host spans and counters say about a window.

The node counts its scheduler thread (``thread_pool.scheduler``), the
REST hand-back of search responses (``http.search_return``) and its
garbage collections (``runtime.gc``) in ``_nodes/stats``, and annotates
the profiler trace with ``es.*`` host spans on the devices' clock. The
readers under ``metrics/`` share these reductions; each returns None
where the window holds nothing to read (a program without the counters
or spans).

:func:`attribute_idle` names the device's idle time: for each instant
the device is idle, the innermost ``es.*`` span open then (the one that
opened last, the shorter on a tie), or ``uncovered``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce
from benchmark.readers import Window

PREFIX = "es."
UNCOVERED = "uncovered"


def stat(stats: dict, *path: str):
    """``stats[path[0]][path[1]]...``, or None where any key is absent."""
    for key in path:
        if not isinstance(stats, dict) or key not in stats:
            return None
        stats = stats[key]
    return stats


def delta(w: Window, *path: str) -> Optional[float]:
    """The counter at ``path`` at the window's close minus its open."""
    a, b = stat(w.stats_before, *path), stat(w.stats_after, *path)
    if a is None or b is None:
        return None
    return float(b) - float(a)


def per_event_ms(w: Window, time_path: Tuple[str, ...],
                 count_path: Tuple[str, ...]) -> Optional[float]:
    """Δ summed nanoseconds over Δ count, in milliseconds."""
    t, n = delta(w, *time_path), delta(w, *count_path)
    if t is None or n is None or n <= 0:
        return None
    return t / n / 1e6


def window_pct(w: Window, ns: Optional[float]) -> Optional[float]:
    """``ns`` as a share of the window, in percent."""
    if ns is None or w.window_ns <= 0:
        return None
    return 100.0 * ns / w.window_ns


def gc_pause_ns(w: Window) -> Optional[float]:
    """Summed collection time of every generation in the window."""
    gens = stat(w.stats_after, "runtime", "gc", "collectors")
    if not isinstance(gens, dict):
        return None
    parts = [delta(w, "runtime", "gc", "collectors", g,
                   "collection_time_in_nanos") for g in sorted(gens)]
    if any(p is None for p in parts):
        return None
    return sum(parts)


def _idle(dev: dict, window_ns: float) -> List[Tuple[float, float]]:
    """The device's idle intervals inside [0, window_ns)."""
    busy = trace_reduce.merged(dev["ops"] or dev["modules"], 0, window_ns)
    out, at = [], 0.0
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window_ns > at:
        out.append((at, window_ns))
    return out


def host_spans(trace: dict, window_ns: float
               ) -> List[Tuple[float, float, str]]:
    """The ``es.*`` host events that overlap the window, as (start, end,
    name)."""
    out = []
    for name, s, d in trace["host"]:
        s, d = float(s), float(d)
        if name.startswith(PREFIX) and d > 0 and s < window_ns \
                and s + d > 0:
            out.append((s, s + d, name))
    return out


def _attribute_device(idle: List[Tuple[float, float]],
                      spans: List[Tuple[float, float, str]]
                      ) -> Dict[str, float]:
    # one sweep over span edges and idle edges: between two consecutive
    # edges the device is wholly idle or busy and the open spans fixed
    events = []
    for i, (s, e, _n) in enumerate(spans):
        events.append((e, 0, i))          # closes sort before opens
        events.append((s, 2, i))
    for a, b in idle:
        events.append((b, 1, -1))         # idle ends
        events.append((a, 3, -1))         # idle starts
    events.sort()
    out: Dict[str, float] = {}
    open_spans: Dict[int, Tuple[float, float]] = {}
    idle_open = False
    owner: Optional[str] = None
    prev = None
    for t, kind, i in events:
        if prev is not None and idle_open and t > prev:
            name = owner or UNCOVERED
            out[name] = out.get(name, 0.0) + (t - prev)
        prev = t
        if kind == 0:
            open_spans.pop(i, None)
        elif kind == 2:
            s, e, _n = spans[i]
            open_spans[i] = (s, s - e)
        elif kind == 1:
            idle_open = False
        else:
            idle_open = True
        if kind in (0, 2):
            owner = spans[max(open_spans, key=open_spans.get)][2] \
                if open_spans else None
    return out


def attribute_idle(trace: dict, window_ns: float) -> Dict[str, float]:
    """Device-idle seconds inside the window by the innermost ``es.*``
    host span open at each instant, plus ``uncovered``; averaged over
    the devices, largest first."""
    devs = trace["devices"]
    if not devs or window_ns <= 0:
        return {}
    spans = host_spans(trace, window_ns)
    total: Dict[str, float] = {}
    for dev in devs:
        for name, ns in _attribute_device(_idle(dev, window_ns),
                                          spans).items():
            total[name] = total.get(name, 0.0) + ns
    return dict(sorted(((n, ns / len(devs) / 1e9)
                        for n, ns in total.items()),
                       key=lambda kv: -kv[1]))


def idle_under_host_pct(w: Window) -> Optional[float]:
    """Share of the device's idle time in the window that some ``es.*``
    host span covers, in percent; None without a trace, or where the
    trace holds no ``es.*`` span."""
    if w.trace is None or not w.trace["devices"] or w.window_ns <= 0:
        return None
    if not host_spans(w.trace, w.window_ns):
        return None
    idle = attribute_idle(w.trace, w.window_ns)
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * (total - idle.get(UNCOVERED, 0.0)) / total
