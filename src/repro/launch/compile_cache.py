"""Persistent XLA compilation cache for the entry points.

A cold run on the chip compiles every engine program from scratch;
persisting the compiled executables lets the next run on the same
checkout load them instead.  Entry points (``chip_smoke.py``,
``repro.launch.serve``) call :func:`enable_compile_cache` once, before
their first compile — never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed: the cache key includes the directory, so a path that moved
# between runs (a temp name, a pid, a time) would never hit
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / '.jax_cache'


def enable_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when it is set (jax reads it
    itself, so nothing is configured); otherwise ``<checkout>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    jax.config.update('jax_compilation_cache_dir', str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
