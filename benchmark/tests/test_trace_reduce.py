"""The trace reduction on a small recorded trace: 12 ms of a v5e
profile of the kNN cell (module, op and host events), reduced by
``trace_reduce`` and checked against a plain nanosecond timeline."""

import json
from pathlib import Path

import numpy as np

from benchmark import trace_reduce

TRACE = Path(__file__).parent / "data" / "trace_knn_12ms.json"


def load():
    t = json.loads(TRACE.read_text())
    return t, float(t["window_ns"])


def timeline_busy(events, window_ns):
    """Busy nanoseconds by marking each event's interval on a grid."""
    grid = np.zeros(int(window_ns) + 1, bool)
    for _n, s, d in events:
        grid[int(round(s)): int(round(s + d))] = True
    return int(grid.sum())


def test_busy_is_the_union_of_op_intervals():
    t, window = load()
    ops = t["devices"][0]["ops"]
    assert ops, "the extract holds device operations"
    got = trace_reduce.busy_ns(t, window)
    assert abs(got - timeline_busy(ops, window)) <= len(ops)
    assert 0 < got < window


def test_family_time_sums_its_modules():
    t, _ = load()
    mods = t["devices"][0]["modules"]
    want = sum(d for n, _s, d in mods if n.startswith("jit_knn"))
    assert want > 0
    assert trace_reduce.family_ns(t, ("knn",)) == want
    assert trace_reduce.family_ns(t, ("bm25",)) == 0
    assert trace_reduce.kernel_name("jit__bm25_coarse_kernel(12)") == \
        "bm25_coarse_kernel"


def test_breakdown_gaps_and_busy_fill_the_window():
    t, window = load()
    out = trace_reduce.breakdown(t, window, top=1000)
    gaps = sum(s for _n, s in out["idle_gaps"]) * 1e9
    busy = trace_reduce.busy_ns(t, window)
    assert abs(gaps + busy - window) < 1.0
    names = [n for n, _s in out["device_ops"]]
    assert names[0] == "jit_knn_topk_batch"
    assert all("(" not in n for n in names)


def test_only_chip_planes_count():
    """A v5e trace also has an empty "/device:CUSTOM:Megascale Trace"
    plane; counted as a device it would halve every average."""
    assert not trace_reduce.DEVICE_PLANE.match(
        "/device:CUSTOM:Megascale Trace")
    assert trace_reduce.DEVICE_PLANE.match("/device:TPU:0")
