"""Placement of JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py`` and the ``benchmarks/`` scripts before their
first compile — never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing is set here.  Otherwise the cache goes to a
fixed ``<checkout>/.jax_cache``: the directory is part of the cache key, so a
path that moved between runs (a temp name, a pid, a timestamp) never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
