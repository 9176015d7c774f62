"""JAX's persistent compilation cache, for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py``,
``launch/train.py``) call :func:`enable_compile_cache` from ``main`` —
never at import, and never from tests. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here overrides it; otherwise the
cache goes to one fixed directory inside the checkout. The path is part
of the cache key's context, so it is never made from a temporary name, a
pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
