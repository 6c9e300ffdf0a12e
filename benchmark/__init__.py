"""The on-chip benchmark: one command measures the served search path.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix, per-layer metric or kernel
family lives in a file of its own, found by name:

- ``configs/<config>.json`` + ``configs/<config>.py``: the deployment's
  sizes and mapping, its seeded generator, array-built segments and the
  plain numpy reference;
- ``traffic/<traffic>.json``: loop, rate or clients, query class, pool,
  warm-up ladder and the limits of the correctness comparison;
- ``metrics/<metric>.py``: one per-layer metric reader;
- ``roofline/<family>.py``: the work a kernel family needs;
- ``peaks.json``: the chip's published peaks by ``device_kind``.
"""
