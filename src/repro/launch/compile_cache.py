"""Where JAX keeps its persistent compilation cache for this checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and JAX reads it
itself; nothing else is set then.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache``: the directory is part of the cache key, so a
path that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call once from an entry point (a script's ``main``), before the first
    compile — never from library code, so tests leave the cache alone."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # the store's kernels compile in well under JAX's default 1 s threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
