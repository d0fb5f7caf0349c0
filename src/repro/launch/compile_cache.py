"""JAX's persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives at one fixed path
inside the checkout (``<repo>/.jax_cache``, git-ignored).  The path is
part of every cache key, so it never depends on a pid, a timestamp or a
temporary name.  Call ``enable_compile_cache`` before the first compile,
never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Points JAX's persistent compilation cache at ``compile_cache_dir``
    and returns that directory."""
    import jax
    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
