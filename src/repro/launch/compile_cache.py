"""Persistent compilation cache placement for the entry points.

Each chip run of this repository may start on a machine with no
compiled code, and the Pallas kernels plus the scheduler's programs take
seconds to minutes to compile.  JAX keys its persistent cache on the
directory path, so the cache lives at one fixed place in the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to the fixed
    `CHECKOUT_CACHE_DIR`, caching every program however fast it
    compiled (a cold call compiles each of them).  Call it from an
    entry point before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CHECKOUT_CACHE_DIR)
