"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``,
``launch/abm_serve.py``) call :func:`enable_compile_cache` from their
``__main__`` block — never at import, so tests and library users keep
JAX's own default (no persistent cache).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored).  A fixed path: the directory is part
# of the cache key, so a cache that moves never hits.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
    variable itself and nothing here overrides it.  Otherwise the cache
    goes to :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
