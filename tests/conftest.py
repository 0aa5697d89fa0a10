"""Shared test config: CPU-only JAX, fixed seeding helpers, `slow` marker,
and a wall-time limit on every test.

The main test process must stay on ONE device (the mesh tests compile on
placeholder devices inside subprocesses that set their own XLA_FLAGS), so no
device-count flags are set here. Quick local runs: `-m "not slow"` skips the
subprocess lower+compile tests.

Every test's setup and call run under `support.time_limit`: past
`support.DEFAULT_LIMIT_S` (or its own `@pytest.mark.limit(seconds)`) the test
fails with a traceback instead of holding its worker until the suite's own
clock runs out.
"""
from __future__ import annotations

import collections
import os

# One BLAS thread per test process, and (inherited) per child it starts. Set
# before scipy loads its OpenBLAS, the LAPACK behind XLA's CPU eigvalsh: with
# a thread per core in each of several processes at once, OpenBLAS's spinning
# threads starve each other. A beta bank that took 10 s alone took over 300 s
# beside a second process simulating the same bank.
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")
# Every test process, and every child it starts, shares JAX's persistent
# compilation cache, in the entry points' own default directory, down to the
# smallest program: the eager shard_map exchange compiles each of its
# operations as a program of its own, about 1,300 in a 4-round run, and
# would compile them again every round and in every worker.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", ".cache", "jax")))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from support import limit_of, time_limit  # noqa: E402

os.environ.setdefault("JAX_PLATFORMS", "cpu")
jax.config.update("jax_platform_name", "cpu")


# The files that take longest under `-n 6 --dist loadfile` (each file runs
# whole on one worker), longest first, as measured on 8 CPU cores: they go to
# the workers first, so that none of them starts last and sets the suite's
# wall time. The other files follow, most tests first.
LONGEST_FILES = (
    "test_properties_ledger.py",
    "test_async_overlap.py",
    "test_ownership_tree.py",
    "test_properties_variance.py",
    "test_fl.py",
    "test_runtime.py",
    "test_kernels_srht_fused.py",
    "test_properties_unbiased.py",
    "test_ownership_rounds.py",
    "test_obs.py",
    "test_kernels.py",
    "test_codec_pipeline.py",
    "test_archs_smoke.py",
    "test_ownership.py",
    "test_async.py",
    "test_payload_accounting.py",
    "test_distribution.py",
    "test_estimators.py",
    "test_fault_tolerance.py",
    "test_properties_wire.py",
    "test_payload_quantization.py",
    "test_pipeline.py",
)


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):
        # else pytest-xdist hands out the files with the most tests first
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    n_tests = collections.Counter(item.path.name for item in items)

    def rank(item):
        name = item.path.name
        if name in LONGEST_FILES:
            return (LONGEST_FILES.index(name), 0, name)
        return (len(LONGEST_FILES), -n_tests[name], name)

    items.sort(key=rank)  # stable: a file's tests keep their order


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with time_limit(limit_of(item), f"{item.nodeid} (setup)"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with time_limit(limit_of(item), item.nodeid):
        return (yield)


@pytest.fixture
def rng_key():
    """Fixed jax PRNG round key; fold_in per-case for independent draws."""
    return jax.random.key(0)


@pytest.fixture
def np_rng():
    """Fixed numpy Generator for test-data construction."""
    return np.random.default_rng(0)
