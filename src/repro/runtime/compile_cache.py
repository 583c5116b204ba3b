"""JAX's persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.ct_train``,
``benchmarks.run``) call :func:`enable` from their ``main`` — never at
import — so a second run of the same program on the same machine loads its
compiled kernels instead of compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
leaves it alone.  Otherwise the cache lives in ``<checkout>/.jax_cache``
(listed in ``.gitignore``).  The path is part of what a cache hit needs, so
it is fixed: never a temporary, per-process or time-stamped directory.
"""
from __future__ import annotations

import os
import pathlib

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
