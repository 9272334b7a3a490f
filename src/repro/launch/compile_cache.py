"""Persistent compilation cache location for the entry points.

Called by the launchers, the benchmark harness and ``chip_smoke.py`` before
their first compile; library imports never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed and inside the checkout: the directory is part of what a later run
# must find again, so it is never made from a temp name, a pid or the time
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
