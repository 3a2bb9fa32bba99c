"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at the fixed ``.jax_cache``
directory at the root of the checkout (listed in ``.gitignore``).  The path
is part of the cache key, so it never carries a pid, a timestamp or a
temporary name: a directory that moves never hits.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module touches no jax state.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
