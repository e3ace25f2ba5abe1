"""Where the entry points keep JAX's persistent compilation cache.

A cold start compiles every program (minutes for the papers100M-width
steps); the cache lets a later process on the same machine skip that.
"""
from __future__ import annotations

import os

import jax

#: the in-checkout default (listed in .gitignore): a fixed path, so every
#: run from this checkout finds what the previous one cached
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already
    and its setting is left alone; otherwise the cache goes to
    ``DEFAULT_DIR``.  Entry points call this; tests do not."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
