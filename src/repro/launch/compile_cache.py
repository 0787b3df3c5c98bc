"""JAX's persistent compilation cache, configured once by the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile. Library modules and tests never call it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout: a fixed path, because the path is part of what a later run must
find again.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
