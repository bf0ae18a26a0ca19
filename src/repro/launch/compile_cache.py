"""JAX's persistent compilation cache, turned on by every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
itself and no other directory is set here.  Otherwise the cache lives at
``<checkout>/.jax_cache`` — a fixed path, because the directory is part
of what a later run must find again.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))),
    ".jax_cache",
)


def enable() -> str:
    """Turn the cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
