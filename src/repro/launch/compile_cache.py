"""JAX's persistent compilation cache, kept at one fixed place.

A cold process on the chip compiles every program again. The cache lets
a later process with the same programs skip that. JAX keys the cache on
its directory, so the directory must not move between runs: it is never
built from a temp name, a pid or the time.

Call :func:`enable_compile_cache` from an entry point's ``main()``, never
at import time (importing a module must not change JAX's configuration).
"""
from __future__ import annotations

import os
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — this file lives at <checkout>/src/repro/launch/
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def cache_dir_to_set() -> Optional[str]:
    """The directory this process should point JAX at, or ``None`` when
    ``JAX_COMPILATION_CACHE_DIR`` is set and JAX already reads it."""
    if os.environ.get(ENV_VAR):
        return None
    return CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = cache_dir_to_set()
    if path is None:
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", path)
    return path
