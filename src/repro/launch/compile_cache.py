"""Where JAX's persistent compilation cache lives.

A 24-layer prefill bucket plus a decode block takes minutes to compile
cold, so every entry point keeps compiled programs on disk.  The
cache's path is part of its key: a directory that moves between runs
never hits, so the default is fixed inside the checkout.

Call :func:`enable_compile_cache` from an entry point's ``main``, never
on import, and before the first compilation of the process.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache goes to ``.jax_cache/`` at the
    root of the checkout (git-ignored)."""
    if os.environ.get(ENV_VAR):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
