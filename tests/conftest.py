"""Test harness configuration.

Per SURVEY.md §4's lesson: seedable randomized tests + a virtual multi-device
mesh. Tests run on the CPU backend with 8 virtual devices, so sharding tests
exercise real collectives without a chip (the mesh on real chips is driven
by ``chip_smoke.py --four-chips`` on a four-chip host).

IMPORTANT: env vars must be set before jax initializes its backend, hence this
happens at conftest import time, before any test module imports jax. Child
processes the tests start (server nodes) inherit them.
"""

import os
import random

os.environ["JAX_PLATFORMS"] = "cpu"
# tests never write a persistent compilation cache (entry points turn it
# on through elasticsearch_tpu.compile_cache; test servers must not)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption("--seed", action="store", default=None,
                     help="random seed (printed each run for reproducibility)")


@pytest.fixture(autouse=True)
def _seeded_random(request):
    """Every test runs with a printed, reproducible seed (ESTestCase analog)."""
    seed = request.config.getoption("--seed")
    seed = int(seed) if seed is not None else random.SystemRandom().randint(0, 2**31 - 1)
    random.seed(seed)
    np.random.seed(seed % (2**32))
    yield
    # seed is attached to the test report on failure via -ra output
    request.node.user_properties.append(("seed", seed))


@pytest.fixture
def rng():
    return np.random.default_rng(np.random.randint(0, 2**31))
