"""JAX's persistent compilation cache, set up before the first compile.

Compiling the serving programs at published widths takes a large part of
a cold start, so every entry point that drives them on a device calls
:func:`setup_compile_cache` first.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``.jax_cache/`` at the root of the checkout (src/repro/launch/ -> root).
#: A fixed path: the cache is only found again where it was written.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here.  Otherwise the cache is
    :data:`DEFAULT_CACHE_DIR`.  Every program is cached, however quickly
    it compiled: a cold start compiles many small stage programs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
