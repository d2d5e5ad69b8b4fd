"""Where JAX keeps its persistent compilation cache.

A chip run compiles a whole model step, which can cost minutes cold.  The
cache lets a later process reuse what an earlier one compiled, but only if
both look in the same place: the directory is part of how entries are
found again.  ``JAX_COMPILATION_CACHE_DIR``, when set, decides that place
from outside and is left alone; otherwise the cache lives at one fixed,
git-ignored path inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Call before the first compile: JAX fixes the cache location then.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env                      # JAX reads the variable itself
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
