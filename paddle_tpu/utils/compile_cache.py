"""JAX's persistent compilation cache, placed from outside.

Entry points call :func:`enable_compile_cache` before their first
compile — ``chip_smoke.py`` and ``replica_host``'s ``main`` (the benchmark
has its own, ``benchmark/harness.py:setup_compile_cache``).
Importing ``paddle_tpu`` does not: a library that sets process-wide JAX
configuration at import takes the choice away from its caller.

The directory is part of the cache key, so it must not move between
runs: no temporary name, process id or time in it.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this code sets
  no directory.
- otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``), found
  from this file's own path, the same from any working directory.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "default_cache_dir"]

_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the parent of the ``paddle_tpu``
    package directory, independent of the working directory."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory: the environment's when ``JAX_COMPILATION_CACHE_DIR`` is
    set (nothing is set in code then), else the fixed checkout path."""
    env_dir = os.environ.get(_ENV)
    if env_dir:
        return env_dir
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
