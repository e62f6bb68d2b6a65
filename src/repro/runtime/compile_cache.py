"""JAX's persistent compilation cache, kept at one fixed path.

A cache entry is found again only when a later process points at the same
directory, so the path never depends on a temporary name, a process id or
the time. The key holds the op metadata, source paths included, so an
entry is found again by the same code run from the same checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: this file is <checkout>/src/repro/runtime/...
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here. Otherwise, on a TPU, the cache goes to
    ``<checkout>/.jax_cache``. Other backends get no cache: their compiles
    are cheap, and XLA:CPU warns about every executable it loads back.

    The key includes the op metadata: an executable loaded from the cache
    keeps the metadata it was compiled with, and profiles read the
    program's named scopes (``obs.trace``) from it. Without it a program
    that differs from a cached one only in its scopes would load that one.
    """
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
