"""Where JAX keeps its persistent compilation cache.

The cache directory is part of each entry's key, so it must not move from
run to run. Entry points call :func:`use_compile_cache` once at start-up;
nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` — this file lives at src/repro/launch/
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the compile-cache directory in use. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
    nothing; otherwise the cache goes to ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
