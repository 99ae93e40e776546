"""Persistent XLA compilation cache for the entry points (train, serve,
chip_smoke).  Call :func:`use_compile_cache` before the first compile."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path: the same directory must be found again by the next process.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there
    and nothing is changed; otherwise cache in ``<repo>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
