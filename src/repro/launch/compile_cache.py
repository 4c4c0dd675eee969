"""JAX's persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train`` / ``repro.launch.serve``) call
:func:`enable_compile_cache` once at start-up; nothing enables it at
import. The cache key includes the directory, so the path is fixed — no
temporary name, pid or time in it.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (git-ignored): this file is
#: ``<checkout>/src/repro/launch/compile_cache.py``.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Return the cache directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is: JAX reads
    it itself and nothing else is configured. Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
