"""JAX's persistent compilation cache for the repo's entry points.

Call `use_compile_cache()` from an entry point's `main()`, never at
import. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it
and nothing more is set. Otherwise the cache lives at the fixed path
`<repo>/.jax_cache`: the cache directory is part of what a later run
must find again, so it is never built from a temporary name, a pid or
the time.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
