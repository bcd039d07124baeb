"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``
and ``benchmarks/run.py``) call :func:`enable_compile_cache` once, before
they compile anything.  A directory named by ``JAX_COMPILATION_CACHE_DIR``
wins, and JAX reads that variable itself; otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (gitignored).  The path is part of a
cache entry's key, so a directory that moved between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
