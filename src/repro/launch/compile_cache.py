"""Where JAX's persistent compilation cache lives.

Every entry point (``launch.train``, ``fl.run``, ``benchmarks.run``,
``chip_smoke.py``) calls ``enable_compile_cache()`` first, so that processes
sharing a checkout reuse each other's compiled programs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing is set.
- otherwise: ``<checkout>/.cache/jax``, next to ``.cache/beta`` (see
  ``core/beta.py``). The path is fixed because it is part of the cache key:
  a directory that moves never hits.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".cache", "jax")
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
