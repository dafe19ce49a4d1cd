"""Where JAX keeps its persistent compile cache for this repo's entry points.

Called by ``chip_smoke.py`` and the benchmark entry points, never on library
import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of what a later run must find
again.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
