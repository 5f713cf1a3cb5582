"""Persistent compilation cache for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, nothing
here overrides it.  Otherwise the cache lives at a fixed ``.jax_cache/``
in the checkout root (listed in ``.gitignore``).  The directory is part
of what lets a later run find an earlier run's programs, so it never
derives from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (call
    before the first compile) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
