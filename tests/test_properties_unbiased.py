"""Seeded randomized property sweep (shared helpers: property_cases.py).

(a) **Unbiasedness** — E[decode] ≈ true mean for every registered unbiased
sparsifier x quantizer pipeline (top_k is biased by construction and
pairs with ErrorFeedback instead; bf16's deterministic rounding gets a
rounding-sized slack on top of the Monte-Carlo tolerance).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from property_cases import (
    C,
    D,
    K,
    QUANTIZERS,
    UNBIASED_SPARSIFIERS,
    client_chunks,
    make_pipeline,
    mc_estimates,
)
from repro.core import codec
from repro.dist.sharding import chunk_ownership


# ------------------------------------------------------------ (a) unbiasedness


@pytest.mark.parametrize("ownership", [False, True],
                         ids=["monolithic", "ownership"])
@pytest.mark.parametrize("q_name,q_ctor", QUANTIZERS, ids=[q for q, _ in QUANTIZERS])
@pytest.mark.parametrize("sp_name,sp_ctor", UNBIASED_SPARSIFIERS,
                         ids=[s for s, _ in UNBIASED_SPARSIFIERS])
@pytest.mark.parametrize("seed", [0, 1])
def test_unbiasedness_sparsifier_x_quantizer(sp_name, sp_ctor, q_name, q_ctor,
                                             seed, ownership):
    """E[decode] ≈ mean for every unbiased sparsifier x quantizer pipeline
    (CorrelatedQuant's cohort-shared dither included — each client's dither
    stays marginally uniform, so unbiasedness must survive it on every
    sparsifier), monolithic AND owner-partitioned (112 cases)."""
    pipe = make_pipeline(sp_ctor, q_ctor)
    xs = client_chunks(seed)
    plan = chunk_ownership(C, 2) if ownership else None
    xhs = mc_estimates(pipe, xs, plan, trials=160, seed=100 + seed)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    err = np.abs(xhs.mean(0) - xbar)
    sem = xhs.std(0) / np.sqrt(xhs.shape[0]) + 1e-4
    # bf16 rounding is deterministic (not unbiased): allow its rounding size
    slack = 8e-3 if q_name == "bf16" else 5e-3
    assert (err < 6 * sem + slack).all(), (pipe.describe(), float(err.max()))


@pytest.mark.parametrize("ownership", [False, True],
                         ids=["monolithic", "ownership"])
@pytest.mark.parametrize("projection", ["srht", "subsample"])
@pytest.mark.parametrize("seed", [0, 1])
def test_unbiasedness_fused_decode_routes(projection, seed, ownership):
    """Unbiasedness survives the fused kernel decode (docs/DESIGN.md §3.5)
    through BOTH decode routes — monolithic and owner-partitioned — for the
    CG resolvent solve (srht; the ridge eps is compensated exactly by the
    recalibrated beta) and the diagonal closed form (subsample)."""
    pipe = codec.as_pipeline(codec.RandProjSpatial(
        k=K, d_block=D, transform="avg", projection=projection,
        decode_method="fused"))
    xs = client_chunks(seed, rho=0.9)
    plan = chunk_ownership(C, 2) if ownership else None
    xhs = mc_estimates(pipe, xs, plan, trials=160, seed=500 + seed)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    err = np.abs(xhs.mean(0) - xbar)
    sem = xhs.std(0) / np.sqrt(xhs.shape[0]) + 1e-4
    assert (err < 6 * sem + 5e-3).all(), (projection, float(err.max()))


def test_top_k_is_biased_hence_excluded():
    """The counter-property: top_k's E[decode] != mean (that is WHY it pairs
    with ErrorFeedback and sits outside the unbiased sweep)."""
    pipe = codec.as_pipeline(codec.TopK(k=4, d_block=D))
    xs = client_chunks(3)
    xhs = mc_estimates(pipe, xs, None, trials=160, seed=3)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    err = np.abs(xhs.mean(0) - xbar)
    sem = xhs.std(0) / np.sqrt(xhs.shape[0]) + 1e-4
    assert (err > 6 * sem + 5e-3).any()
