"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call :func:`enable_compile_cache` once, before their first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this module sets no other directory. Otherwise the cache lives in
``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed path,
because the directory is part of what a later process must find again.

JAX writes an entry only for a compile that took at least
``jax_persistent_cache_min_compile_time_secs`` (1 s by default). Most DGO
engines compile in less on the CPU, so every later process would compile
them again; the helper lowers that threshold to 0 so that each distinct
program is written once.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
