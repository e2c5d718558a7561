"""JAX's persistent compilation cache for the entry points.

Called from ``main`` functions only, never at import: a library that
changed a process-wide setting on import would surprise its callers.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other path is set here; otherwise the cache lives at a fixed path inside
the checkout (``.jax_cache/``, gitignored), because the path is part of
the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (this file is src/repro/launch/compile_cache.py)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> pathlib.Path:
    """Where compiled programs are cached: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV)
    return pathlib.Path(env) if env else CHECKOUT / ".jax_cache"


def enable_compile_cache() -> pathlib.Path:
    """Turn the persistent cache on for this process; returns its path."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
