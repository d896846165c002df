"""JAX's persistent compilation cache, placed from outside or in the checkout.

Every entry point that compiles a model (``chip_smoke.py``,
``benchmarks/run.py``, ``python -m repro.launch.serve``) calls
:func:`enable_compile_cache` once, before its first compile.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: Fallback cache directory: fixed and inside the checkout (git-ignored),
#: because the path is part of what a later run must find again.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and no other directory is set here. Without it the cache goes to
    :data:`DEFAULT_DIR`.
    """
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
