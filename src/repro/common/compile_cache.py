"""Where JAX keeps its persistent compilation cache.

The cache's path is part of its key, so it is fixed: the directory that
``JAX_COMPILATION_CACHE_DIR`` names, where that is set (JAX reads the
variable itself), else ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory.  Call before the first compile."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
