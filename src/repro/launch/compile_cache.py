"""Persistent JAX compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/quickstart.py``) call ``enable_compile_cache()`` once before
their first compile; library modules never do.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, because the directory is part of the cache key and a path that moves
between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
