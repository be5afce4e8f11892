"""JAX's persistent compilation cache for the entry points (examples,
benchmarks, ``chip_smoke.py``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here.  Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: a fixed path, since the directory is part of the cache key and a
path built from a temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
