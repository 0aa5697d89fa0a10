"""Shared constants and helpers of the chunk-ownership tests
(``test_ownership.py``, ``test_ownership_tree.py``,
``test_ownership_rounds.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec

D = 128
K = 16


def client_tree(np_rng, n=6):
    return {
        "w": jnp.asarray(np_rng.standard_normal((n, 40, 20)), jnp.float32),
        "b": jnp.asarray(np_rng.standard_normal((n, 33)), jnp.float32),
    }


def assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


ALL_ESTIMATORS = [
    codec.RandK(k=K, d_block=D),
    codec.RandK(k=K, d_block=D, shared_randomness=False),
    codec.RandKSpatial(k=K, d_block=D, transform="avg"),
    codec.RandProjSpatial(k=K, d_block=D, transform="avg"),
    codec.RandProjSpatial(k=K, d_block=D, transform="avg",
                          shared_randomness=False),
    codec.TopK(k=K, d_block=D),
    codec.Wangni(k=K, d_block=D),
    codec.Induced(k=K, d_block=D),
    codec.Identity(d_block=D),
    codec.SparseProj(k=K, d_block=D, transform="avg"),
    codec.SparseProj(k=K, d_block=D, transform="avg",
                     shared_randomness=False),
    codec.Pipeline([codec.RandK(k=K, d_block=D), codec.Int8Quant()]),
    codec.Pipeline([codec.RandProjSpatial(k=K, d_block=D), codec.Bf16Quant()]),
    codec.Pipeline([codec.SparseProj(k=K, d_block=D), codec.Int8Quant()]),
]
