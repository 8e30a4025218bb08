"""Where JAX keeps its persistent compilation cache.

A chip run recompiles every jitted program unless the cache survives
between processes.  The cache's path is part of its key, so it lives at
one fixed place: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (this file is <root>/src/repro/launch/compile_cache.py)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
