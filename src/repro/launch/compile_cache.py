"""JAX's persistent compilation cache at one fixed place per checkout.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and this
sets nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``: the
path is part of the cache key, so it never contains a temporary name, a
process id or a time. Call it from a program's ``main()``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
