"""JAX's persistent compilation cache for the entry points.

Call :func:`use_compile_cache` from an entry point's ``main`` (never from a
library import): it keeps compiled programs where ``JAX_COMPILATION_CACHE_DIR``
says when that is set, and otherwise at one fixed path inside the checkout,
``<checkout>/.jax_cache`` (git-ignored). The path is part of the cache key,
so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env          # JAX reads the variable itself
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
