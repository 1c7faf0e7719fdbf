"""JAX's persistent compilation cache, placed from outside the program.

Every entry point calls :func:`enable_compile_cache` before its first
jitted call. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the
directory from it and this module names no other. Otherwise the cache
sits at one fixed path inside the checkout, ``<repo>/.jax_cache``
(git-ignored): the path is part of what a later run must find again, so
it is never built from a temporary name, a process id or the clock.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Every compile is cached, however small or fast: the simulation
    kernels compile in well under JAX's default one-second threshold,
    and a flush kernel compiles again for each slot capacity a run
    reaches.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
