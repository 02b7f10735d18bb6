"""Where JAX keeps its persistent compilation cache.

Entry points that drive the chip (``chip_smoke.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` before their first compile; importing the
library never touches the cache.
"""
from __future__ import annotations

import os

import jax


def use_compile_cache(checkout: str) -> str:
    """Place the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    — a fixed path, so a later run from the same checkout finds what an
    earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
