#!/usr/bin/env python3
"""The control of ``correct``: the reference in bfloat16 in the
program's place.

For each seed, the cell's data and query pool are made as a run makes
them, a sample of ``check`` queries is drawn as a run draws it, and the
configuration's ``control`` (the reference's scoring with every
operation in bfloat16, on the default device) answers them. Those
answers go through the same comparison as a run's, and each number is
printed beside the cell's limit: a sound limit is exceeded here.

    python benchmark/control.py --workload <cell> --seeds 1,2,3

The benchmark's runs never call this; it sets the upper reading of each
limit, on the chip at the cell's own size (``PERF.md`` gives them).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Sequence

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def control_numbers(workload: str, seed: int, n_docs: int = 0,
                    check: int = 0, answers: Sequence[str] = ("control",)
                    ) -> dict:
    """For each name in ``answers`` (a method of the configuration's
    ``Data`` that answers in the program's place: ``control``, or a
    planted fault), each compared number beside its limit; the data and
    the sample are made once for all of them."""
    import numpy as np

    from benchmark import harness
    from benchmark.run import HERE, cell_spec, load_module
    _bench, _cell, cfg, traffic, _m = cell_spec(workload)
    mod = load_module(HERE / "configs" / f"{cfg['name']}.py",
                      f"benchmark_config_{cfg['name']}")
    data = mod.Data(cfg, seed, n_docs=n_docs)
    pool = data.pool(traffic, seed)
    rng = np.random.default_rng([seed, 3])
    n = check or int(traffic["check"])
    specs = [pool[i] for i in rng.choice(len(pool), n, replace=False)]
    refs = data.reference(specs)
    got = {}
    for name in answers:
        out = []
        for docs, scores in getattr(data, name)(specs):
            out.append({"hits": {"hits": [
                {"_id": str(int(d)), "_score": float(s),
                 **({"_source": data.source(int(d))}
                    if data.source(int(d)) is not None else {})}
                for d, s in zip(docs, scores)]}})
        numbers = harness.compare(out, lambda j: refs[j], data.source,
                                  harness.layout(data).id_space)
        got[name] = {k: {"value": numbers[k], "limit": traffic["limits"][k]}
                     for k in traffic["limits"]}
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--answers", default="control",
                    help="comma-separated methods of the configuration "
                         "that answer in the program's place: control, or "
                         "a planted fault such as index_wide")
    args = ap.parse_args(argv)
    import jax
    d = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        got = control_numbers(args.workload, seed,
                              answers=args.answers.split(","))
        for name, out in got.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "answers": name,
                              "device": f"{d.platform}/{d.device_kind}",
                              "compared": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
