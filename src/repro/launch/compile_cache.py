"""JAX's persistent compilation cache, placed for entry points.

``enable_compile_cache()`` is called by entry points (``chip_smoke.py``,
``benchmarks/run.py``) before their first compile, never at import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no other path.  Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path, because the path is part of what a later run must find again.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
