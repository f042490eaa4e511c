"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`use_compile_cache` once at
startup; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed directory in the checkout: the cache is keyed by its path, so a
# path built from a temporary name, a pid or the time would never hit
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here overrides it; otherwise the cache goes to the checkout's
    ``.jax_cache/``.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
