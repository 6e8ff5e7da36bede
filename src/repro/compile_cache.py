"""JAX's persistent compilation cache for the command-line entry points
(``chip_smoke.py``, ``benchmarks/``, ``examples/``), so a second process
reuses the programs the first one compiled. The library never calls
this at import, and the tests never call it."""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/compile_cache.py -> the repository root
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing else is set; otherwise the cache lives at the fixed
    ``<repo root>/.jax_cache``, never at a per-run path, so every run
    of this checkout finds what the previous one wrote."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
