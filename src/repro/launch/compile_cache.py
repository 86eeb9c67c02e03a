"""JAX's persistent compilation cache at a fixed place.

Compiling the release executables dominates a cold run on the chip, and
a later run finds them only if it looks in the same directory, so the
path never comes from a temp name, a pid or the time. Entry points call `enable_compile_cache()` once, before
their first compile; importing the library never does.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Return the cache directory in use, setting it first if need be.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed. Otherwise the cache goes to ``.jax_cache`` at the
    root of this checkout.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
