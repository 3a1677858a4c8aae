"""JAX's persistent compilation cache for the programs that run on a chip.

Call :func:`enable_compile_cache` at the start of an entry point, before
anything compiles; JAX settles on a cache directory at its first compile.
Importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# A fixed place in the checkout, so that every run of it finds what an
# earlier run compiled; listed in .gitignore.
_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is the cache, and JAX already
    reads it by itself: no other directory is set.  Otherwise the cache is
    ``.jax_cache/`` at the root of the checkout.
    """
    env = os.environ.get(_ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return str(_DEFAULT_DIR)
