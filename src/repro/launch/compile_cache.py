"""JAX's persistent compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and nothing
here overrides it.  Otherwise the cache lives at one fixed path inside the
checkout (``<repo>/.jax_cache``, git-ignored): the directory is part of the
cache's key, so it is never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on before the first compile; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
