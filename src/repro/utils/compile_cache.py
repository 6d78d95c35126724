"""Persistent XLA compilation cache for the entry points that run on a chip.

A cold run on the TPU compiles every program again; the persistent cache
lets the next run of the same checkout load them instead. Tests leave it
off.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed on purpose: the directory is part of how an entry is found again,
# so a path built from a temporary name, a pid or the time would never hit
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and it is left alone. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
