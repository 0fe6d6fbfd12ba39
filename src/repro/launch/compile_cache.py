"""Persistent XLA compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``,
``benchmarks.ci_bench``) call :func:`enable_compile_cache` once at start-up;
importing the library never touches JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (src/repro/launch/ -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
    path, because the directory is part of what a later process must find
    again. The minimum compile time is lowered to zero so the many graph
    kernels that compile in under JAX's default one second are cached too.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
