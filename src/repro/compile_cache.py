"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``examples/*.py``)
call ``enable_compile_cache()`` once, before their first compile; nothing
calls it at import. The cache directory is part of the cache's key, so it
never moves between runs: ``JAX_COMPILATION_CACHE_DIR`` when that is set,
otherwise ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
import pathlib

ROOT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
