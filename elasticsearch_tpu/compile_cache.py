"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``bench.py``, the two server
entry points in ``rest/server.py``) call :func:`enable_compile_cache`
once, before their first compile. Tests never call it, so a test run
writes no cache.

The cache key includes the directory, so the path is fixed: either the
one the environment gives in ``JAX_COMPILATION_CACHE_DIR`` (which JAX
reads itself; this module then sets nothing), or ``.jax_cache`` at the
root of the checkout — never a temp, pid- or time-derived path.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parent.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
