"""JAX persistent compilation cache for the entry points.

Entry points (``launch.serve``, ``launch.train``, ``chip_smoke.py``, the
benchmark scripts) call :func:`enable_compile_cache` once, before their
first compile; nothing turns the cache on at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing. Otherwise the cache lives at ``<checkout>/.jax_cache`` — a
fixed path, since the path is part of the cache key, and inside the
checkout, so a run reads and writes nothing around it.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the repository checkout: src/repro/launch/compile_cache.py -> root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory (``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache``)."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
