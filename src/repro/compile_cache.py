"""JAX's persistent compilation cache, set up once by every entry point.

``repro.cli``, ``chip_smoke.py``, ``benchmarks/run.py`` and
``examples/quickstart.py`` call :func:`enable_compile_cache` before their
first compile, so processes that compile the same programs (a benchmark
followed by its measurement run, successive CLI calls) share them.
"""
from __future__ import annotations

import os
from pathlib import Path

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout, so every process finds what an earlier
#: one stored (a directory that moves never hits)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it:
    this leaves that setting alone and sets no other directory.
    """
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
