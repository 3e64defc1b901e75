"""JAX's persistent compilation cache for the launch entry points.

A full-width step takes tens of seconds to compile on a TPU host, and each
fresh process starts with nothing compiled; the persistent cache lets
processes that share a cache directory reuse one another's compiles.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: the cache only hits when later runs look in the same place
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache is ``<repo>/.jax_cache``.
    Call before the first compile. ``LIBTPU_INIT_ARGS`` is left as it is:
    a flag this program needs is appended to it, never written over it.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
