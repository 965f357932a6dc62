"""JAX persistent compilation cache at a fixed place.

Entry points (``repro.launch.serve.main``, ``benchmarks/serve_bench.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` before their first
compile; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <repo>/.jax_cache (gitignored).  The directory is part of the cache
# key, so it is a fixed path, never built from a temp name, pid or time.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :data:`CACHE_DIR`,
    unless ``JAX_COMPILATION_CACHE_DIR`` is set (JAX then already uses
    that directory and nothing is changed).  Returns the directory in
    use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
