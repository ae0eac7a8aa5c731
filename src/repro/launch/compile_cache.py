"""JAX's persistent compilation cache, kept at one fixed directory.

Every entry point that compiles (``chip_smoke.py``, ``examples/fl_emnist.py``,
``repro.launch.train``, ``repro.launch.aggregator``, ``benchmarks/run.py``)
calls ``enable_compile_cache()`` before its first compile, so repeated runs
on one machine reuse compiled programs. The directory is part of the
cache's key: it never depends on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` when
    that is set (JAX reads it too; no other directory is set), else at
    ``<repo>/.jax_cache``. Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
