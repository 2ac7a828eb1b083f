"""JAX's persistent compilation cache, placed for this checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`use_compile_cache` once at start-up; importing the library
never does.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads
it itself.  Otherwise the cache lives at a fixed path inside the
checkout: the directory is part of the cache key, so a path derived from
a temporary name, a pid or the clock would never hit.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
