"""JAX's persistent compilation cache for the entry points (``chip_smoke.py``,
``benchmarks/run.py``, the examples). Library modules never turn it on."""
from __future__ import annotations

import os

import jax

# a fixed path: the cache directory is part of what a later run has to find
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads the
    variable itself); otherwise the cache sits at ``<repo>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # most of a job's programs compile in well under JAX's default 1 s
    # threshold, yet together they are most of a cold run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
