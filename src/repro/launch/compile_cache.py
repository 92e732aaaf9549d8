"""JAX's persistent compilation cache for the entry points.

Called from ``main()`` of each entry point (``chip_smoke.py``,
``benchmarks/run.py``, ``launch/serve.py``), never at library import: a
library that picks a cache directory on import overrides its caller's.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed path inside the checkout: the cache key includes the directory,
# so a path built from a temporary name, a pid or the time would never hit
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR` (``<checkout>/.jax_cache``, ignored by git).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
