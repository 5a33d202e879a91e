"""JAX's persistent compilation cache for the entry points that run on a chip.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable_compile_cache`
before their first compile, so a second run of the same programs loads
them instead of compiling them again.  Tests do not call it: their
compile counts must not depend on what an earlier run left behind.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: Used where ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed path at the
#: checkout root (git-ignored).  It must not move between runs, because the
#: directory is where a later run looks for what this one compiled.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
