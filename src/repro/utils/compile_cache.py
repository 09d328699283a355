"""JAX's persistent compilation cache, at one fixed place.

Every entry point (``fed_train``, ``train``, ``serve``, ``chip_smoke.py``)
calls ``use_compile_cache()`` before it compiles anything.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
directory is set.  Otherwise the cache lives in ``<repo>/.jax_cache``
(gitignored).  The path is fixed — never a temporary directory, a process
id or a time — so that the next run finds what this one compiled.
``FederatedEngine`` calls ``key_on_metadata()``, so that a cached program
never answers with another build's name scopes.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def key_on_metadata() -> None:
    """Key the persistent cache on the programs' metadata too.

    By default JAX strips the metadata (``op_name``, source locations) from
    the key, so a program that differs from a cached one only in its name
    scopes loads the cached executable, with the cached program's names,
    and a device trace then attributes its operations by names that are
    not this build's.  Source paths enter the key relative to the checkout
    (``jax_hlo_source_file_canonicalization_regex``), so a moved or copied
    checkout still finds what it compiled; an edit to a line that a
    program's operations trace through compiles that program again.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{REPO_ROOT}{os.sep}"))
