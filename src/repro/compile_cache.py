"""JAX's persistent compilation cache, placed for the entry points.

A cold TPU compile of a replay program takes seconds; the cache keeps the
compiled executables across processes.  Its directory is part of the
cache key, so it is a fixed path: ``$JAX_COMPILATION_CACHE_DIR`` when that
is set (JAX reads the variable itself), otherwise ``.jax_cache`` at the
root of the checkout.  Entry points call :func:`enable_compile_cache`
before their first compile; importing a module never turns it on.

Every executable is cached, however fast it compiled (JAX's default
skips those under a second).  A process pays its start-up compiles anew
otherwise: the eager ops of a replay, and a chunk program whose kernel
compiles in under a second, add seconds to every start.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "CHECKOUT_CACHE", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for every executable and
    return its directory: the environment's if it names one (JAX already
    reads it), else the fixed in-checkout directory, which this sets."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
