"""JAX's persistent compilation cache, kept at one fixed path.

A cache entry is found again only when a later process points at the same
directory, so the path never depends on a temporary name, a process id or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: this file is <checkout>/src/repro/runtime/...
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise, on a TPU, the cache goes to
    ``<checkout>/.jax_cache``. Other backends get no cache: their compiles
    are cheap, and XLA:CPU warns about every executable it loads back.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
