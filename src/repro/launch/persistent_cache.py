"""JAX's persistent compilation cache: compiled executables kept on disk.

This is XLA's on-disk cache, read back by later processes on the same
machine.  It is a different thing from the *structural compile cache*
(``repro.backend.compile_cache``), which memoizes ``jax.jit`` wrappers in
memory for the life of one process.

Entry points call ``enable_persistent_cache()`` before their first compile;
importing ``repro`` never turns it on.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it and this module sets no directory.  Otherwise the cache
lives at ``.jax_cache/`` in the checkout: a fixed path, because a directory
that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# JAX caches only compiles slower than 1 s by default.  A block kernel
# compiles in tens to hundreds of milliseconds, well below that, and one
# workload compiles a few dozen distinct kernels, so every compile is kept:
# a cache read costs less than any of them.
MIN_COMPILE_SECS = 0.0


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        DEFAULT_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return jax.config.jax_compilation_cache_dir
