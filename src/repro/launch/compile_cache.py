"""Persistent XLA compilation cache for the entry points that run on a chip.

A process that finds a program in the cache skips its compilation, which on
a TPU can take longer than the work itself.  The cache only hits when later
runs look in the same place, so the directory is fixed: never a temporary
directory, a pid or a timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache", "REPO_CACHE_DIR"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the first compilation.  Where `JAX_COMPILATION_CACHE_DIR` is
    set, JAX reads it itself and nothing else is set here; otherwise the
    cache lives in `<repo>/.jax_cache` (ignored by git).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
