"""JAX's persistent compilation cache, at one fixed place.

Every entry point (``fed_train``, ``train``, ``serve``, ``chip_smoke.py``)
calls ``use_compile_cache()`` before it compiles anything.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else
is set.  Otherwise the cache lives in ``<repo>/.jax_cache`` (gitignored).
The path is fixed — never a temporary directory, a process id or a time —
so that the next run finds what this one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
