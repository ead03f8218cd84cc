"""JAX's persistent compilation cache for the entry points.

A fresh process compiles every program again; on a TPU that is a large
part of a short run.  JAX keys its cache by the cache directory's path, so
the directory must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads it itself), else the fixed ``.jax_cache``
directory at the root of this checkout.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Call before
    the process compiles anything."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
