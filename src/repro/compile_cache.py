"""JAX's persistent compilation cache, for the programs' entry points.

Only entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`enable_compile_cache`; importing the library changes nothing.
"""

from __future__ import annotations

import os

import jax


def enable_compile_cache(repo_root: str) -> str:
    """Keep compiled programs across processes; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache is ``<repo_root>/.jax_cache``:
    a fixed path, since the path is part of what a cached entry is found by.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(repo_root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
