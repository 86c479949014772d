"""Persistent XLA compile cache for the entry points.

A cold compile of a 28-layer step is tens of seconds, and serving
compiles one program per step kind. The cache key includes the
cache directory, so it must sit at a fixed place: the directory that
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads it
itself), else ``<repo root>/.jax_cache``, derived from this file's
location.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
