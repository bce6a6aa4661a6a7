"""Persistent XLA compilation cache for the repository's entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable_compile_cache`
before their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already caches there and no other directory is set. Otherwise the cache
lives at a fixed path inside the checkout (``.jax_cache``, ignored by git):
the path is part of what a later run must find again, so it is never built
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Also keeps every compile, however short: the Pallas kernels compile in
    well under JAX's default one-second threshold, and there are many.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
