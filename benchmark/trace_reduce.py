#!/usr/bin/env python3
"""From a profiler trace of the window to the device's numbers.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote with
``jax.profiler.ProfileData`` into plain lists: for each device plane the
events of its module line and its op line, and the host planes' events.
The reductions work on those lists alone, so a small recorded extract
(``--extract``) checks them without a chip:

- :func:`busy_ns`: the union of the intervals in which an operation ran
  on a device, inside the window, averaged over the devices;
- :func:`family_ns`: the summed device time of the modules of a kernel
  family (jitted functions named ``<prefix>...``, with or without a
  leading underscore), averaged over devices;
- :func:`breakdown`: the ten modules that took most device time, and the
  ten longest idle gaps, each named by the host event that covers most
  of it (among events no longer than four gaps).

Times are nanoseconds from the start of the trace.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

# a chip's plane; "/device:CUSTOM:..." planes hold no chip's operations
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


def load(trace_dir: str) -> dict:
    """``{"devices": [{"name", "modules", "ops"}], "host": [...]}`` from
    the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = "modules" if line.name in MODULE_LINES else \
                    "ops" if line.name in OP_LINES else None
                if key:
                    dev[key].extend((e.name, e.start_ns, e.duration_ns)
                                    for e in line.events)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def describe(trace_dir: str) -> dict:
    """Planes, lines, event counts and sample names: what to look at by
    hand before trusting the reductions."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            ev = list(line.events)
            lines.append({"line": line.name, "events": len(ev),
                          "first": [(e.name, e.start_ns, e.duration_ns)
                                    for e in ev[:4]]})
        out.append({"plane": plane.name, "lines": lines[:24]})
    return {"file": files[-1], "bytes": os.path.getsize(files[-1]),
            "planes": out}


def merged(events: Sequence[Event], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """The union of the events' intervals, clipped to [lo, hi]."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _n, s, d in events
                   if s + d > lo and s < hi)
    out: List[List[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _busy_events(dev: dict) -> List[Event]:
    return dev["ops"] or dev["modules"]


def busy_ns(trace: dict, window_ns: float) -> float:
    devs = trace["devices"]
    if not devs:
        return 0.0
    return sum(sum(b - a for a, b in merged(_busy_events(d), 0, window_ns))
               for d in devs) / len(devs)


def module_name(name: str) -> str:
    """``jit_bm25_flat(12)`` -> ``jit_bm25_flat``."""
    return re.sub(r"\(\d+\)$", "", name)


def kernel_name(name: str) -> str:
    """``jit__bm25_coarse_kernel(7)`` -> ``bm25_coarse_kernel``: the
    jitted function's name, without its module's decoration."""
    return module_name(name).removeprefix("jit_").lstrip("_")


def family_ns(trace: dict, prefixes: Sequence[str]) -> float:
    devs = trace["devices"]
    if not devs:
        return 0.0
    return sum(d for dev in devs for n, _s, d in dev["modules"]
               if kernel_name(n).startswith(tuple(prefixes))) / len(devs)


def breakdown(trace: dict, window_ns: float, top: int = 10) -> dict:
    per: Dict[str, float] = {}
    devs = trace["devices"]
    for dev in devs:
        for n, _s, d in dev["modules"] or dev["ops"]:
            per[module_name(n)] = per.get(module_name(n), 0.0) + d
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps: List[Tuple[float, float]] = []
    if devs:
        busy = merged(_busy_events(devs[0]), 0, window_ns)
        edges = [0.0] + [x for ab in busy for x in ab] + [window_ns]
        gaps = sorted(((edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]),
                      key=lambda g: g[0] - g[1])[:top]
    named = []
    for a, b in gaps:
        best, cover = "no host event", 0.0
        for n, s, d in trace["host"]:
            if d > 4 * (b - a) or s >= b or s + d <= a:
                continue
            o = min(b, s + d) - max(a, s)
            if o > cover:
                best, cover = n, o
        named.append([best, (b - a) / 1e9])
    return {"device_ops": [[n, t / len(devs) / 1e9] for n, t in ops],
            "idle_gaps": named}


def extract(trace: dict, lo_ns: float, hi_ns: float) -> dict:
    """The events of [lo_ns, hi_ns), shifted to start at 0: a small
    recorded trace for the tests."""
    def cut(events):
        return [[n, s - lo_ns, d] for n, s, d in events
                if s >= lo_ns and s + d <= hi_ns]
    return {"window_ns": hi_ns - lo_ns,
            "devices": [{"name": d["name"], "modules": cut(d["modules"]),
                         "ops": cut(d["ops"])} for d in trace["devices"]],
            "host": cut(trace["host"])}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--extract", nargs=3, metavar=("LO_NS", "HI_NS", "OUT"))
    args = ap.parse_args(argv)
    if args.describe:
        print(json.dumps(describe(args.trace_dir), indent=1))
        return 0
    trace = load(args.trace_dir)
    if args.extract:
        lo, hi, out = float(args.extract[0]), float(args.extract[1]), \
            args.extract[2]
        with open(out, "w") as f:
            json.dump(extract(trace, lo, hi), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
