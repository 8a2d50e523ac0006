"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call `use_compile_cache` before their first compile, so a rerun of the
same checkout loads compiled programs instead of compiling them again.
Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The in-checkout cache directory: fixed, because the path is part of
#: the cache key (a directory that moves never hits).  Git-ignored.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here.  Otherwise the cache goes to `CACHE_DIR`."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
