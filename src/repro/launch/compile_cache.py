"""Persistent XLA compile cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/paper_campaign.py``) call :func:`enable_compile_cache` once at
start-up; importing ``repro`` never does.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this sets nothing.  Otherwise the cache goes
to one fixed, git-ignored directory inside the checkout: the path is part of
the cache key, so it never comes from a temp name, a pid or the time.
"""

from __future__ import annotations

import os

#: the checkout root (``src/repro/launch`` -> three levels up)
CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                        ".."))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory and return
    that directory."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE)
    return DEFAULT_CACHE
