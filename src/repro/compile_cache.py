"""Where JAX keeps its persistent compilation cache — one rule for every
entry point (``chip_smoke.py``, ``benchmarks/run.py``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the cache
goes there; nothing else is set.  Otherwise the cache goes to ``.jax_cache``
inside the checkout: a fixed path (the path is part of a cache entry's key,
so a per-process or temporary directory would never hit), listed in
``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str | os.PathLike) -> str:
    """Turn on JAX's persistent compilation cache for a process started from
    checkout ``root``; returns the directory in use."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    path = str(Path(root).resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
