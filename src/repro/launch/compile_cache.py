"""JAX's persistent compilation cache for the repo's entry points.

`chip_smoke.py`, `repro.launch.serve` and `benchmarks.run` call
`enable_compile_cache()` first thing; importing the package never does, so
tests and library users keep JAX's defaults.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and the cache
lives there: no other directory is set in code, so whoever runs the
program can place the cache. Otherwise it goes to `<checkout>/.jax_cache`
(listed in `.gitignore`), a fixed path, because the path is part of what a
cached entry is found by. Every compile is cached, however quick, so the
small kernels of a cold run are found again too.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
