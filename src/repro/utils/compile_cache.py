"""JAX's persistent compilation cache, placed by the entry points.

The library never turns the cache on when it is imported: an entry point
(``chip_smoke.py``, ``benchmarks/run.py``, ``scripts/train_release.py``)
calls :func:`enable_compile_cache` before its first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache"]

#: where the environment places the cache; it wins over the default
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str | Path) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and
    no other directory is set.  Otherwise it lives at ``<checkout>/.jax_cache``:
    a fixed path, because the path is part of what a cached program is
    found by, so later runs from the same checkout hit it.
    """
    path = os.environ.get(CACHE_ENV) or str(
        Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
