"""JAX's persistent compilation cache, at a path a later run finds again.

A served network compiles one program per (bucket, resolution) pair; at
224x224 that is seconds per program on a TPU.  The persistent cache lets a
second process (a respawned worker, the next smoke or benchmark run) load
those programs instead of compiling them.  The cache is keyed by its
directory, so the directory must not move between runs: it is either the
one ``JAX_COMPILATION_CACHE_DIR`` names, or a fixed ``.jax_cache/`` at the
repository root (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
    no other directory is set here.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
