"""JAX's persistent compilation cache, kept at one fixed place.

The cache directory is part of each entry's key, so a path that moves
between runs never hits.  ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set
(JAX reads it itself and nothing here overrides it); otherwise the cache
lives at ``<checkout>/.jax_cache``.  Entry points (``chip_smoke.py``,
``repro.launch.train``) call :func:`enable_compile_cache` once at start-up.
"""
from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "compile_cache_dir", "enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(_ENV) or REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Point JAX at :func:`compile_cache_dir` and return it."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
