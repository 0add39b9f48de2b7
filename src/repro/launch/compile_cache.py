"""One place that decides where JAX keeps its persistent compile cache.

Entry points call :func:`enable_compile_cache` before their first compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache in
that directory and nothing else is set here. Otherwise the cache goes to
``.jax_cache`` at the root of the checkout: a fixed path, because the path
is part of each entry's key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
