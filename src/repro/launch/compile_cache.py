"""Where the entry points keep JAX's persistent compilation cache.

A cold chip run compiles every kernel and step from scratch; a warm cache
skips that.  The cache only helps if every run looks in the same place, so
the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the repository root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
