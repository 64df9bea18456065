"""JAX's persistent compilation cache for the training entry points.

A full-width step takes tens of seconds to compile, and the chip machine
starts every call cold.  ``launch/train.py`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` once, after importing JAX and before the first
compile.  The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(and then no other), else the fixed ``<checkout>/.jax_cache``: the path is
part of the cache key, so a name that changes per run never hits.
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
