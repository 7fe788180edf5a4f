"""Persistent compilation cache for the entry points (``chip_smoke.py``,
``repro.launch.train``, ``benchmarks``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives in the fixed
``.jax_cache/`` at the repository root: the directory is part of how a
cached program is found again, so it never depends on a temporary name,
a process id or the time.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the kernels and most session programs compile in under JAX's
    # default 1 s threshold, yet together they are most of a cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
