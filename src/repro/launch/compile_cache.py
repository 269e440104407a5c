"""JAX's persistent compilation cache for this repo's entry points.

Entry points (``repro.launch.count`` / ``analyze`` / ``serve_graph``,
``benchmarks.run``, ``chip_smoke.py``) call :func:`enable_compile_cache`
once before their first compile; library imports set nothing.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
path is set here.  Otherwise the cache lives at one fixed directory of
the checkout, ``.jax_cache/`` (git-ignored): the path is part of the
cache key, so it never carries a temp name, pid or timestamp.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
