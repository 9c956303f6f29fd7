"""Where JAX keeps its persistent compilation cache.

A served model compiles its prefill, decode and step executables for every
shape bucket; the persistent cache lets a later process load them instead
of compiling again.  The cache key includes its directory, so the path
must not move between runs: ``$JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads it itself), else ``.jax_cache`` at the root of the checkout.
Entry points call :func:`enable_compile_cache` before their first compile;
nothing does at import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
