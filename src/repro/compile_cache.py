"""JAX's persistent compilation cache for the repo's entry points.

Every Pallas kernel here is jitted per program length and grid shape, so a
cold process pays each compile again.  `enable()` keeps compiled programs
on disk between processes:

  * where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this module sets no other directory;
  * otherwise the cache lives in ``.jax_cache/`` at the checkout root - a
    fixed path, because the path is part of what makes an entry hit.

The minimum compile time an entry must have is lowered so the
second-scale kernel compiles are kept too.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return jax.config.jax_compilation_cache_dir
