"""JAX's persistent compilation cache, switched on by the entry points.

A program's command-line entry (``launch.train``, ``serve.http``, the
benchmark runner, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before its first compile; importing the library never does.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and nothing
is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because a cache keyed to a temporary, per-process or dated
directory is never found again.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
