"""Persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` runs before an entry point's first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing else is configured. Otherwise the cache goes to the fixed
``<checkout>/.jax_cache``: the directory is part of each entry's key,
so it must not move between runs (no temp name, pid or time in it).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
