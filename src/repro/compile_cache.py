"""JAX's persistent compilation cache, for the entry points.

A process that compiles a full-width model spends most of its start-up in
XLA; with the cache on, the next run with the same programs loads them
instead.  Entry points call `enable_compile_cache()` once before their
first compile; importing a module never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed cache directory inside the checkout (listed in .gitignore).  The
#: path is part of the cache key, so it never depends on a temp dir, a PID
#: or the time
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads the
    variable itself, and no other is set); otherwise the fixed
    `CHECKOUT_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
