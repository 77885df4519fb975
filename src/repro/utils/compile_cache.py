"""JAX's persistent compilation cache, shared by every entry point.

A compiled step is written once and read back by the next process with the
same program, so a second run skips the compile (tens of seconds at the
paper's widths).  The directory is fixed, never a temporary name, so the
next process finds it.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here; otherwise the cache lives at :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
