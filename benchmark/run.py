#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (all of it in ``setup_s``, from process start to the window's
open): create the cell's index over REST; generate the configuration's
data from the seed; build each shard's segments from arrays and install
them through the engine's snapshot-restore entry; where the index has
several shards, pack the mesh plane with one search; warm every batch
size and work bucket of the traffic's pool through ``_msearch``; start
the load generator (``loadgen.py``, a process of its own without JAX).
Then the window: ``--seconds`` of the traffic mix over HTTP to the node
in this process. After it: compare the window's answers with the
configuration's plain numpy reference, and print one JSON line: the
end-to-end metrics with ``--trace 0``, the per-layer metrics (under a
profiler trace of the window) with ``--trace 1``.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not beside it.
``--sweep RATES`` runs the open loop at each rate instead (the one-time
search for the knee) and prints one line per rate.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = ROOT / "benchmark"
COMPILE_CACHE = ROOT / ".jax_cache"


def process_age_s() -> float:
    """Seconds since this process was started, from /proc."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def cell_spec(workload: str) -> tuple:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])
    metrics = {"end_to_end": [m for m in bench["end_to_end"] if mine(m)],
               "per_layer": [m for m in bench["per_layer"] if mine(m)]}
    return bench, cell, cfg, traffic, metrics


def start_jax(chips: int, require_tpu: bool):
    """The devices, or None (with the reason on stderr) where they are
    not what the cell needs."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    COMPILE_CACHE.mkdir(exist_ok=True)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction: the cache holds a few tens of MB, and eviction's
    # bookkeeping files fail where an entry was written without them
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        log(f"run: JAX found no TPU (platform {devices[0].platform!r})")
        return None
    if len(devices) < chips:
        log(f"run: {len(devices)} device(s), the cell needs {chips}")
        return None
    return devices


def start_loadgen(plan: dict, sweep: str = ""):
    cmd = [sys.executable, str(HERE / "loadgen.py")]
    if sweep:
        cmd += ["--sweep", sweep]
    gen = subprocess.Popen(cmd, cwd=str(ROOT), stdin=subprocess.PIPE,
                           stdout=subprocess.PIPE, text=True)
    gen.stdin.write(json.dumps(plan) + "\n")
    gen.stdin.flush()
    if gen.stdout.readline().strip() != "ready":
        raise RuntimeError("load generator did not start")
    return gen


def main(argv=None, *, require_tpu: bool = True, n_docs: int = 0) -> int:
    """One run of a cell. ``require_tpu=False`` and ``n_docs`` (a size
    below the configuration's) are for ``benchmark/tests`` alone, which
    drive whole runs on the CPU at 2^12 docs; the command line reaches
    neither."""
    t_start = time.perf_counter() - process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated open-loop rates: find the knee")
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic, metrics = cell_spec(args.workload)

    sys.path.insert(0, str(ROOT))
    try:
        import elasticsearch_tpu
    except ImportError as e:
        log(f"run: the program is not beside the benchmark: {e}")
        return 2
    if Path(elasticsearch_tpu.__file__).resolve().parent.parent != ROOT:
        log("run: elasticsearch_tpu is not this checkout's")
        return 2
    devices = start_jax(int(cell["chips"]), require_tpu)
    if devices is None:
        return 1
    from benchmark import harness, readers, trace_reduce
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"].get(
        devices[0].device_kind)
    if peaks is None and require_tpu:
        log(f"run: {devices[0].device_kind!r} is not in peaks.json")
        return 1

    data_mod = load_module(HERE / "configs" / f"{cfg['name']}.py",
                           f"benchmark_config_{cfg['name']}")
    t0 = time.perf_counter()
    data = data_mod.Data(cfg, args.seed, n_docs=n_docs)
    log(f"setup: {data.n_docs} docs generated in "
        f"{time.perf_counter() - t0:.1f}s")
    srv = harness.Served()
    gen = None
    trace_dir = None
    try:
        harness.create_index(srv, data.index, data.index_body())
        t0 = time.perf_counter()
        lay = harness.layout(data)
        sizes = harness.install_shards(srv, data.index, data)
        log(f"setup: segments {sizes} built and restored in "
            f"{time.perf_counter() - t0:.1f}s")
        pool = data.pool(traffic, args.seed)
        bodies = [json.dumps(data.body(s)) for s in pool]
        if lay.n_shards > 1:
            t0 = time.perf_counter()
            mesh = harness.pack_mesh_plane(srv, data.index, bodies[0])
            log(f"setup: mesh plane packed, "
                f"{mesh.get('resident_bytes_per_device')} bytes a device, "
                f"in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        batches = harness.warm_ladder(
            [data.blocks(s) for s in pool], traffic["warm_batches"],
            classes=None if lay.warm_class is None else
            [lay.warm_class(s) for s in pool])
        warmed = harness.warm_up(srv, data.index, bodies, batches)
        log(f"setup: warm-up {len(batches)} batches, {warmed} queries in "
            f"{time.perf_counter() - t0:.1f}s")
        plan = {"port": srv.port, "path": f"/{data.index}/_search",
                "bodies": bodies, "traffic": traffic,
                "seconds": args.seconds, "seed": args.seed}
        gen = start_loadgen(plan, args.sweep)
        before = srv.stats()
        if args.trace:
            import jax
            trace_dir = tempfile.mkdtemp(prefix="trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        t_go = time.perf_counter()
        gen.stdin.write("go\n")
        gen.stdin.flush()
        for line in gen.stdout:
            if line.strip() == "closed":
                break
            if args.sweep:
                print(line.strip(), flush=True)
        window_ns = (time.perf_counter() - t_go) * 1e9
        if args.trace:
            import jax
            jax.profiler.stop_trace()
        after = srv.stats()
        out = json.loads(gen.stdout.readline())
        if lay.n_shards > 1:
            m0, m1 = harness.mesh_counters(before), \
                harness.mesh_counters(after)
            log("window: mesh " + ", ".join(
                f"{k} +{m1[k] - m0[k]}" for k in m1) +
                f", max_occupancy {after['mesh_plane'].get('max_occupancy')}")
        p0, p1 = (s.get("device_plane") or {} for s in (before, after))
        c0, c1 = (int((s.get("device_profile") or {}).get(
            "total_compiles") or 0) for s in (before, after))
        log("window: plane " + ", ".join(
            f"{k} +{int(p1.get(k) or 0) - int(p0.get(k) or 0)}"
            for k in ("rerank_escalations", "quantized_exact_fallbacks")) +
            f", depths {p1.get('rerank_depth_histogram')}, compiles "
            f"+{c1 - c0}")
        gen.wait(60)
        if args.sweep:
            return 0
        peaks_each = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices[: int(cell["chips"])]]
        peak = max(peaks_each)
        log(f"device: peak bytes in use by chip {peaks_each}")
    finally:
        if gen is not None and gen.poll() is None:
            gen.kill()
            gen.wait()
        srv.close()

    # -- the window's answers against the reference ----------------------
    records, answers = out["records"], out["answers"]
    ok, parsed = [], []
    for rec, raw in zip(records, answers):
        resp = json.loads(raw) if raw is not None and rec[4] == 200 else None
        if resp is not None and (resp.get("_shards", {}).get("failed")
                                 or "hits" not in resp):
            resp = None
        ok.append(resp is not None)
        parsed.append(resp)
    t0 = time.perf_counter()
    import numpy as np
    check = int(traffic["check"])
    sample = list(range(len(records)))
    if len(sample) > check:
        rng = np.random.default_rng([args.seed, 3])
        sample = sorted(rng.choice(len(records), check,
                                   replace=False).tolist())
    refs = data.reference([pool[records[i][0]] for i in sample])
    numbers = harness.compare([parsed[i] for i in sample],
                              lambda j: refs[j], data.source,
                              lay.id_space)
    limits = traffic["limits"]
    correct = all(numbers[k] <= limits[k] for k in limits)
    log(f"check: {len(sample)} answers against the reference in "
        f"{time.perf_counter() - t0:.1f}s")

    lat = [1e3 * (r[3] - r[1]) if good else math.inf
           for r, good in zip(records, ok)]
    w = readers.Window(
        traffic=traffic, query_class=traffic["class"],
        seconds=args.seconds, latencies_ms=lat,
        late_ms=[1e3 * (r[2] - r[1]) for r in records if r[2] is not None],
        stats_before=before, stats_after=after, data=data,
        sent=[pool[r[0]] for r in records],
        peaks=peaks or {},
        window_ns=window_ns,
        extra={"setup_s": setup_s, "records": records, "ok": ok})
    if args.trace:
        w.trace = trace_reduce.load(trace_dir)
    kind = "per_layer" if args.trace else "end_to_end"
    values = {}
    for m in metrics[kind]:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                             f"benchmark_metric_{m['name']}")
        v = reader.read(w)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": ok.count(False), "metrics": values,
              "device": device}
    if args.trace:
        busy = trace_reduce.busy_ns(w.trace, window_ns)
        device["busy_s"] = busy / 1e9
        device["window_s"] = window_ns / 1e9
        result["breakdown"] = trace_reduce.breakdown(w.trace, window_ns)
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["compared"] = {k: {"value": numbers[k], "limit": limits[k]}
                          for k in limits}
    for k in limits:
        log(f"compared {k} {numbers[k]!r} limit {limits[k]!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
