"""Where JAX keeps its persistent compilation cache: the one place that
sets it.

A compile cache is keyed by its directory, so a directory that moves never
hits. ``$JAX_COMPILATION_CACHE_DIR``, when set, names the directory and no
other is used; otherwise the cache lives at the checkout's fixed
``.jax_cache``. Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the
test suite's ``conftest.py``) call :func:`configure_compile_cache` once at
start-up; library code never repoints it.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "configure_compile_cache"]


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, when that is unset, at :data:`DEFAULT_CACHE_DIR`; returns the path.

    Compiles that take at least half a second are cached (the replay
    bodies and Pallas kernels take seconds; tiny helper jits are not worth
    a disk entry).
    """
    import jax

    path = os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
