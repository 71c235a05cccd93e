"""JAX's persistent compilation cache, placed from outside.

A cache directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself and this module sets no other path), otherwise
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os

import jax

#: fixed in-checkout default (gitignored)
DEFAULT_DIR = os.path.realpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, kernels that compile in under a second included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
