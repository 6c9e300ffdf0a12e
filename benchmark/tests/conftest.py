"""The benchmark's own tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# four host devices: the four-shard cell's mesh spans four chips
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4"
                               ).strip()
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture(autouse=True)
def _free_mesh_planes():
    """Mesh planes are process-wide and keyed by segment: each whole run
    packs new ones, so free them once a test is done."""
    yield
    mod = sys.modules.get("elasticsearch_tpu.ops.device_segment")
    if mod is not None:
        mod.MESH_PLANES.clear()
