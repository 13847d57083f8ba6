"""Where the entry points keep JAX's persistent compilation cache.

The cache's path is part of its key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing else
  is configured here.
* otherwise — ``<checkout>/.jax_cache`` (listed in ``.gitignore``), a fixed
  path beside the sources.

Entry points (``chip_smoke.py``, the benchmarks) call
:func:`enable_compile_cache` before their first compile. The library and
the test suite never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
