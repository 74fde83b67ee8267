"""Where JAX keeps its persistent compilation cache.

A cold start on the chip compiles every step program, which can be a large
part of a short run; the cache lets a second process, or a second run in
the same checkout, load them instead. The cache directory is part of the
cache key, so it is one fixed path, never a temporary name.
"""
from __future__ import annotations

import os

import jax

# <repo>/.jax_cache, listed in .gitignore
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile and return
    its directory. A JAX_COMPILATION_CACHE_DIR in the environment wins:
    JAX reads it itself and nothing is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
