"""JAX's persistent compilation cache for the programs that run on a chip.

`enable` is called by the programs themselves (`chip_smoke.py`,
``python -m benchmarks.bench_runtime``), before their first compile.
Importing `repro` or running the tests never turns the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
other directory is set. Otherwise the cache lives in ``.jax_cache/`` at
the root of this checkout: a fixed path, because the path is part of the
cache key, so a second run in the same checkout finds the first run's
entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable"]

# src/repro/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Every compile is cached, however short: a Pallas kernel compiles in a
    second or two, under JAX's default one-second threshold.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
