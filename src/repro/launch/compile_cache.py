"""Persistent JAX compilation cache for the command-line entry points.

A Newton step takes minutes to compile, so every entry point (``chip_smoke.py``,
``repro.launch.register``, ``repro.launch.serve_registration``,
``benchmarks/registration_bench.py``) keeps its compiled programs on disk.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives in a fixed directory of the
checkout: a path that changed between runs (temporary, per process or per
time) would never be hit again. Tests do not call this.
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; returns the directory this
    call set, or None where ``JAX_COMPILATION_CACHE_DIR`` already places it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
