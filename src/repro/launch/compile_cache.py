"""JAX persistent compilation cache for the launchers.

Full-depth programs take tens of seconds each to compile, and a serving run
compiles one per dispatch shape bucket. :func:`enable_compile_cache` is
called at the start of a launcher's ``main`` (never at import):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code.
* otherwise, on an accelerator: the cache goes to :data:`CACHE_DIR`, one
  fixed, git-ignored directory in the checkout. The path is part of the
  cache key, so a fixed directory is what lets a later run hit it.
* otherwise, on the CPU backend (tests): no cache. The reduced configs
  compile in seconds, and XLA:CPU executables read back from a cache are
  checked against the host's CPU features and log a mismatch error.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; returns its directory
    (None where there is none)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
