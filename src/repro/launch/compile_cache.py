"""Persistent XLA compile cache for every entry point.

A cold run on the chip compiles the whole pipeline, and compiles dominate
its wall time; JAX's persistent cache lets a later process reuse them.
Each CLI (``repro.launch.*``), ``benchmarks/run.py`` and ``chip_smoke.py``
calls :func:`enable_compile_cache` before its first compile.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when it is set — nothing else is chosen;
* otherwise ``<checkout>/.jax_cache``, one fixed path (listed in
  ``.gitignore``).  The path is part of the cache key, so a directory that
  moved between runs (a temporary name, a PID, a time) would never hit.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache — this file is <checkout>/src/repro/launch/
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def cache_dir() -> str:
    """The directory the persistent compile cache uses."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at :func:`cache_dir`."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
