"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that compile large programs (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start-up;
nothing calls it on import.  A cache only hits when its directory stays
put from run to run, so the directory is never a temporary name, a pid or
a timestamp."""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]


def enable_compile_cache(checkout: str) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory; otherwise the cache is ``.jax_cache/``
    inside ``checkout`` (the repository root, listed in ``.gitignore``)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
