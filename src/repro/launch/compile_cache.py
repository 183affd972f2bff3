"""JAX's persistent compilation cache, placed by the entry points.

Entry points (``launch/train.py``, ``launch/serve.py``, ``benchmarks/run.py``
and ``chip_smoke.py``) call ``use_compile_cache()`` once before they
compile; nothing calls it at import.  When ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX has already read it and the setting is left alone.  Otherwise the
cache goes to ``<checkout>/.jax_cache``: a fixed path, because the path is
part of what a later process must find again.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
