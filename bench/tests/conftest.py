"""The benchmark's own tests: on the CPU, at tiny sizes (``tiny.py``).

Run from the repository root: ``python -m pytest bench/tests``. They are
not in the tier-1 suite (``pyproject.toml`` points pytest at ``tests/``).
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def compile_cache(tmp_path_factory):
    """One persistent compilation cache for the session: every run rebuilds
    its programs, and the same tiny programs recur across tests."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(tmp_path_factory.mktemp("jax-cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    from bench.tests import tiny

    return tiny.make_root(str(tmp_path_factory.mktemp("root")))
