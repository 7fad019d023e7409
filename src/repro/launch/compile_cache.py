"""Persistent XLA compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself and
nothing here overrides it. Otherwise the cache lives at one fixed path
inside the checkout, ``<repo>/.jax_cache`` — the directory is part of the
cache key, so a path that moved between runs (a temp name, a pid, a time)
would never hit.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or the in-checkout default; returns the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
