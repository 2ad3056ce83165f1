"""JAX's persistent compilation cache, placed from outside the program.

Entry points that drive the chip call `enable_compile_cache()` before
anything compiles.  `JAX_COMPILATION_CACHE_DIR`, where set, is JAX's own
setting and is used as it is.  Otherwise the cache lives at one fixed,
git-ignored directory of the checkout: the directory is part of what a
cache entry is found by, so it must not move from run to run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Every
    compile is cached, however short: the Pallas kernels compile in well
    under a second, below JAX's default one-second floor."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
