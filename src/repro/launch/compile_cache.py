"""Where JAX keeps its persistent compilation cache.

Entry points that run on the chip (``chip_smoke.py`` and the benchmark
scripts) call :func:`enable_compile_cache` once, before their first
compile. Nothing calls it on import. The cache directory is part of the
cache key, so it must not move between runs: a temp-, pid- or
time-based path would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
_REPO = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and that
    directory is the cache: no other is set. Otherwise the cache goes in
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
