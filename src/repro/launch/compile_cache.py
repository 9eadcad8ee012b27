"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.train``, ``benchmarks.run``)
call :func:`configure_compile_cache` first, before anything compiles;
importing this module changes nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout this module lives in (``<checkout>/src/repro/launch/``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    The path is fixed, never built from a temporary name, a pid or the
    time: the directory is part of what a later process must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
