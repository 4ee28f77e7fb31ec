"""JAX's persistent compilation cache for the repo's entry points.

Called by ``chip_smoke.py``, ``launch/serve.py`` and ``benchmarks/run.py``
(never by tests). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here; otherwise the cache lives in
``.jax_cache/`` at the repository root. The path is part of what JAX
keys a cache on, so it must stay fixed from run to run.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
