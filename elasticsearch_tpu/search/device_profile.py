"""Device observatory: XLA compile/recompile tracking, per-request
compile attribution, and the profiled-jit wrapper every device kernel
routes through.

PR 8 made every *request* observable; the layer that actually determines
TPU throughput — XLA compilation and per-kernel execution — stayed
invisible. Every ops file hand-tunes pow2 shape bucketing ("one distinct
gather shape costs a full XLA compile (~seconds)", ops/bm25.py
``qb_bucket``; "pow-2 shapes keep the compile cache to ~9 entries",
ops/ivf.py ``search``) yet nothing measured whether those invariants
held: a padding-policy regression would surface only as an unexplained
p99 cliff. This module is the measurement:

- :func:`profiled_jit` / :func:`profiled_callable` — THE way a kernel
  under ``ops/``, ``search/`` or ``parallel/mesh.py`` gets staged. The
  wrapper jits the function and, per concrete call, detects whether the
  call compiled (the jitted function's own executable-cache size is the
  authoritative signal; a host-side shape-bucket mirror is the fallback
  when that private surface moves) and reports to the process-global
  :class:`DeviceProfile` registry. A grep-guard test pins raw-jit call
  sites at zero, the PR 8 "unknown fallback reason pinned at zero"
  precedent — an uninstrumented new kernel fails CI.
- :class:`DeviceProfile` (process-global ``DEVICE_PROFILE``, the PLANES /
  TELEMETRY one-accelerator-per-process precedent): per kernel-family
  compile counts vs cache hits, compile wall-time (total / max), live
  shape-bucket cardinality and a **recompile-storm detector** (a
  counter + slow-compile log line when a family crosses a configurable
  distinct-compile rate). A cache-hit call costs one executable-cache
  size read on each side of the dispatch and a counter increment; the
  shape-bucket label is built only when the call compiled.
- Request attribution rides the PR 8 contextvar trace: jitted functions
  cannot self-report, so the host-side wrapper is the dispatch seam —
  a compile inside an active :class:`~.telemetry.SearchTrace` adds a
  ``compile`` span (``profile: true`` responses show ``compile_ms``) and
  flags the trace so slow logs mark first-compile requests. Profile-off
  responses stay byte-identical: nothing here ever touches a response.

Timing semantics: compile wall time is the first-call wall time for a
shape bucket (trace + XLA compile dominate it). Device time is the
profiler trace's, never this module's: JAX dispatch is asynchronous and
the observatory never pays a device sync.

Import discipline: this module imports only the stdlib and its sibling
``telemetry`` at load time (``jax`` lazily, at first wrap) so the ops
modules can import it at module top without cycling through the search
package's serving stack.
"""

from __future__ import annotations

import inspect
import logging
import time
from typing import Any, Callable, Dict, Optional, Tuple

from elasticsearch_tpu.search import telemetry

logger = logging.getLogger(__name__)

# per-family bound on the shape-bucket map: bucket labels derive from
# call shapes, so a pathological caller must not grow node memory
# forever; compiles themselves stay exactly counted
MAX_BUCKETS_PER_FAMILY = 256

_TRACER_TYPE: Any = None


def _tracer_type():
    """jax's Tracer type, resolved lazily (public path first)."""
    global _TRACER_TYPE
    if _TRACER_TYPE is None:
        from jax.core import Tracer
        _TRACER_TYPE = Tracer
    return _TRACER_TYPE


def _describe_dynamic(v: Any) -> str:
    """Shape-bucket component for one traced argument: dtype[shape] for
    arrays, the bare type name for weakly-typed scalars (jax caches by
    dtype, not value — a per-value label would explode the bucket map
    without any recompile behind it)."""
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(int(d)) for d in shape)}]"
    if v is None:
        return "None"
    return type(v).__name__


class FamilyProfile:
    """One kernel family's observatory record."""

    __slots__ = ("name", "compiles", "cache_hits", "compile_ns_total",
                 "compile_ns_max", "shapes", "compile_marks", "storms")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.cache_hits = 0
        self.compile_ns_total = 0
        self.compile_ns_max = 0
        # shape-bucket label -> compile count (cardinality == the live
        # compile-cache size the pow2 bucketing invariants promise)
        self.shapes: Dict[str, int] = {}
        # recent compile times (monotonic seconds) for the storm window
        self.compile_marks: list = []
        self.storms = 0


class DeviceProfile:
    """Process-global compile/execute registry (one accelerator per
    process — the PLANES / BREAKERS / TELEMETRY precedent). Surfaced as
    the ``_nodes/stats`` ``"device_profile"`` section and merged
    fleet-wide into ``_cluster/stats``."""

    def __init__(self):
        self._families: Dict[str, FamilyProfile] = {}
        self.enabled = True
        # storm detector: more than ``storm_threshold`` compiles of a
        # family inside ``storm_window_s`` is a recompile storm — the
        # bucketing invariant broke (or a workload churns shapes) and
        # every compile costs seconds of serving capacity
        self.storm_threshold = 8
        self.storm_window_s = 60.0
        # individual compiles slower than this also log (a single
        # multi-second XLA compile mid-serving deserves a line even
        # without a storm)
        self.slow_compile_ms = 1000.0

    def configure(self, storm_threshold: Optional[int] = None,
                  storm_window_s: Optional[float] = None,
                  slow_compile_ms: Optional[float] = None) -> None:
        if storm_threshold is not None:
            self.storm_threshold = int(storm_threshold)
        if storm_window_s is not None:
            self.storm_window_s = float(storm_window_s)
        if slow_compile_ms is not None:
            self.slow_compile_ms = float(slow_compile_ms)

    def configure_from_state(self, state) -> None:
        """Refresh the storm/slow-compile knobs from committed cluster
        settings (``search.device_profile.storm_*``), memoized on the
        state version like the plane registries — the parse must not tax
        the per-search hot path it observes."""
        from elasticsearch_tpu.utils.settings import state_settings_key
        key = state_settings_key(state)
        if key is not None and key == getattr(self, "_cfg_version", None):
            return
        self._cfg_version = key
        from elasticsearch_tpu.utils.settings import (
            SEARCH_DEVICE_PROFILE_SLOW_COMPILE,
            SEARCH_DEVICE_PROFILE_STORM_THRESHOLD,
            SEARCH_DEVICE_PROFILE_STORM_WINDOW, setting_from_state,
        )
        self.configure(
            storm_threshold=setting_from_state(
                state, SEARCH_DEVICE_PROFILE_STORM_THRESHOLD),
            storm_window_s=setting_from_state(
                state, SEARCH_DEVICE_PROFILE_STORM_WINDOW),
            slow_compile_ms=1000.0 * setting_from_state(
                state, SEARCH_DEVICE_PROFILE_SLOW_COMPILE))

    def family(self, name: str) -> FamilyProfile:
        fam = self._families.get(name)
        if fam is None:
            fam = self._families[name] = FamilyProfile(name)
        return fam

    # -- recording --------------------------------------------------------

    def on_compile(self, family: str, label: str, dur_ns: int) -> None:
        fam = self.family(family)
        fam.compiles += 1
        fam.compile_ns_total += int(dur_ns)
        fam.compile_ns_max = max(fam.compile_ns_max, int(dur_ns))
        fam.shapes[label] = fam.shapes.get(label, 0) + 1
        while len(fam.shapes) > MAX_BUCKETS_PER_FAMILY:
            fam.shapes.pop(next(iter(fam.shapes)))
        # storm detection over a sliding window of compile marks
        now = time.monotonic()
        marks = fam.compile_marks
        marks.append(now)
        horizon = now - self.storm_window_s
        while marks and marks[0] < horizon:
            marks.pop(0)
        stormed = len(marks) > self.storm_threshold
        if stormed:
            fam.storms += 1
            # reset the window so one sustained churn counts as one
            # storm per threshold-crossing, not one per extra compile
            del marks[:]
        if stormed or dur_ns / 1e6 >= self.slow_compile_ms:
            logger.warning(
                "slow-compile: family [%s] shape [%s] compiled in "
                "%.1fms (%d distinct shape buckets, %d compiles total%s)",
                family, label, dur_ns / 1e6, len(fam.shapes),
                fam.compiles,
                ", RECOMPILE STORM" if stormed else "")
        # request attribution: the active trace (if any) gains a compile
        # span and the first-compile flag slow logs print
        telemetry.record_compile(family, dur_ns)

    # -- surfaces ---------------------------------------------------------

    def total_compiles(self) -> int:
        return sum(f.compiles for f in self._families.values())

    def compiles_by_family(self) -> Dict[str, int]:
        return {name: fam.compiles
                for name, fam in sorted(self._families.items())}

    def snapshot(self) -> Dict[str, Any]:
        families: Dict[str, Any] = {}
        for name, fam in sorted(self._families.items()):
            families[name] = {
                "compiles": fam.compiles,
                "cache_hits": fam.cache_hits,
                "compile_ms_total": round(fam.compile_ns_total / 1e6, 3),
                "compile_ms_max": round(fam.compile_ns_max / 1e6, 3),
                "shape_buckets": len(fam.shapes),
                "recompile_storms": fam.storms,
            }
        return {
            "families": families,
            "total_compiles": self.total_compiles(),
            "total_cache_hits": sum(
                f.cache_hits for f in self._families.values()),
            "recompile_storms": sum(
                f.storms for f in self._families.values()),
            "storm_threshold": self.storm_threshold,
            "storm_window_s": self.storm_window_s,
        }

    def reset(self) -> None:
        self._families.clear()


DEVICE_PROFILE = DeviceProfile()


class ProfiledJit:
    """A jitted kernel routed through the device observatory.

    Call-compatible with the jitted function it wraps (``lower`` passes
    through). Tracer arguments (this kernel inlined inside another traced
    program) bypass profiling entirely — only concrete dispatches are
    device programs worth attributing."""

    def __init__(self, family: str, fn: Optional[Callable] = None,
                 static_argnames: Tuple[str, ...] = (),
                 jit_kwargs: Optional[Dict[str, Any]] = None,
                 jitted: Optional[Callable] = None):
        if not family:
            raise ValueError("profiled kernels must name their family")
        import jax
        self.family = family
        self._static = frozenset(
            (static_argnames,) if isinstance(static_argnames, str)
            else static_argnames)
        if jitted is None:
            jitted = jax.jit(fn, static_argnames=static_argnames,
                             **(jit_kwargs or {}))
        self._jitted = jitted
        self.__name__ = getattr(fn, "__name__", family) \
            if fn is not None else family
        self.__qualname__ = getattr(fn, "__qualname__", family) \
            if fn is not None else family
        self.__doc__ = fn.__doc__ if fn is not None else None
        self.__wrapped__ = fn if fn is not None else jitted
        params: Tuple[str, ...] = ()
        if fn is not None:
            try:
                params = tuple(inspect.signature(fn).parameters)
            except (TypeError, ValueError):
                params = ()
        self._params = params

    # -- passthrough ------------------------------------------------------

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        """The jitted function's executable-cache size — the
        authoritative compiled-or-not signal."""
        return int(self._jitted._cache_size())

    # -- the profiled call ------------------------------------------------

    def _label(self, args, kwargs) -> str:
        parts = []
        params = self._params
        for i, a in enumerate(args):
            name = params[i] if i < len(params) else None
            if name is not None and name in self._static:
                parts.append(f"{name}={a!r}")
            else:
                parts.append(_describe_dynamic(a))
        for k in sorted(kwargs):
            v = kwargs[k]
            if k in self._static:
                parts.append(f"{k}={v!r}")
            else:
                parts.append(f"{k}={_describe_dynamic(v)}")
        return "/".join(parts)

    def __call__(self, *args, **kwargs):
        reg = DEVICE_PROFILE
        if not reg.enabled:
            return self._jitted(*args, **kwargs)
        tracer = _tracer_type()
        if any(isinstance(a, tracer) for a in args) or \
                any(isinstance(v, tracer) for v in kwargs.values()):
            # inlined inside an outer traced program: the OUTER profiled
            # kernel owns the compile attribution
            return self._jitted(*args, **kwargs)
        before = self._cache_size()
        t0 = time.monotonic_ns()
        out = self._jitted(*args, **kwargs)
        dur_ns = time.monotonic_ns() - t0
        if self._cache_size() > before:
            reg.on_compile(self.family, self._label(args, kwargs), dur_ns)
        else:
            reg.family(self.family).cache_hits += 1
        return out


def profiled_jit(family: str, *, static_argnames: Tuple[str, ...] = (),
                 **jit_kwargs):
    """Decorator: stage ``fn`` with jax.jit AND route every concrete
    call through the device observatory. THE replacement for a bare
    ``partial(jax.jit, ...)`` under ``ops/`` and ``search/`` — the
    grep-guard test pins raw jit call sites there at zero."""
    def wrap(fn: Callable) -> ProfiledJit:
        return ProfiledJit(family, fn, static_argnames=static_argnames,
                           jit_kwargs=jit_kwargs)
    return wrap


def profiled_callable(family: str, stageable: Callable,
                      **jit_kwargs) -> ProfiledJit:
    """Jit + profile an already-staged callable (the shard_map kernel
    factories in parallel/mesh.py): the jit happens HERE so factory call
    sites never spell a raw jit themselves."""
    import jax
    return ProfiledJit(family,
                       jitted=jax.jit(stageable, **(jit_kwargs or {})))


def merge_device_profile_sections(sections) -> Dict[str, Any]:
    """Coordinator-side fleet merge of per-node ``device_profile``
    sections (``_cluster/stats``'s section-filtered fan-out): counters
    sum, compile-time maxima take the max."""
    families: Dict[str, Dict[str, Any]] = {}
    totals = {"total_compiles": 0, "total_cache_hits": 0,
              "recompile_storms": 0}
    for section in sections:
        if not section:
            continue
        for key in totals:
            totals[key] += int(section.get(key) or 0)
        for name, entry in (section.get("families") or {}).items():
            agg = families.get(name)
            if agg is None:
                agg = families[name] = {
                    "compiles": 0, "cache_hits": 0,
                    "compile_ms_total": 0.0, "compile_ms_max": 0.0,
                    "shape_buckets": 0, "recompile_storms": 0}
            agg["compiles"] += int(entry.get("compiles") or 0)
            agg["cache_hits"] += int(entry.get("cache_hits") or 0)
            agg["compile_ms_total"] = round(
                agg["compile_ms_total"]
                + float(entry.get("compile_ms_total") or 0.0), 3)
            agg["compile_ms_max"] = max(
                agg["compile_ms_max"],
                float(entry.get("compile_ms_max") or 0.0))
            agg["shape_buckets"] += int(entry.get("shape_buckets") or 0)
            agg["recompile_storms"] += int(
                entry.get("recompile_storms") or 0)
    return {"families": dict(sorted(families.items())), **totals}
