"""Seeded randomized property sweep (shared helpers: property_cases.py).

(b) **Lemma 4.1-style variance ordering** — at rho -> 1,
MSE(rand_proj_spatial) <= MSE(rand_k_spatial) <= MSE(rand_k): the
correlation-aware decoders strictly pay off where correlation exists.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from property_cases import D, K, client_chunks, mc_estimates
from repro.core import codec
from repro.dist.sharding import chunk_ownership
from repro.launch.mesh import make_mesh


# ------------------------------------------- (b) variance ordering at rho -> 1


@pytest.mark.parametrize("ownership", [False, True],
                         ids=["monolithic", "ownership"])
@pytest.mark.parametrize("n", [4, 8])
@pytest.mark.parametrize("k", [4, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lemma_41_variance_ordering_high_rho(n, k, seed, ownership):
    """At rho -> 1 the paper's ordering holds (24 cases):

        MSE(rand_proj_spatial) <= MSE(rand_k_spatial) <= MSE(rand_k)

    and survives the owner-partitioned decode unchanged."""
    xs = client_chunks(seed, n=n, c=1, rho=0.995)
    plan = chunk_ownership(1, 2) if ownership else None
    xbar = np.asarray(jnp.mean(xs, axis=0))

    def mc_mse(spec):
        pipe = codec.as_pipeline(spec)
        xhs = mc_estimates(pipe, xs, plan, trials=150, seed=200 + seed)
        return float(np.mean(np.sum((xhs - xbar[None]) ** 2, axis=(1, 2))))

    mse_rk = mc_mse(codec.RandK(k=k, d_block=D))
    mse_rks = mc_mse(codec.RandKSpatial(k=k, d_block=D, transform="avg"))
    mse_rps = mc_mse(codec.RandProjSpatial(k=k, d_block=D, transform="avg"))
    # small MC slack; the expected gaps are factors, not percents
    assert mse_rps <= mse_rks * 1.05, (mse_rps, mse_rks)
    assert mse_rks <= mse_rk * 1.05, (mse_rks, mse_rk)
    assert mse_rps < mse_rk * 0.9, (mse_rps, mse_rk)


@pytest.mark.parametrize("ownership", [False, True],
                         ids=["monolithic", "ownership"])
def test_sparse_proj_variance_ordering_high_rho(ownership):
    """Lemma 4.1-style ordering for the cheap-encode member: at rho -> 1
    SparseProj's Gram-resolvent decode never loses to plain Rand-k at equal
    budget, and wins clearly on average across the (n, k, seed) grid —
    correlation-awareness survives the very-sparse maps."""
    plan = chunk_ownership(1, 2) if ownership else None
    ratios = []
    for n in (4, 8):
        for k in (4, 8):
            for seed in range(3):
                xs = client_chunks(seed, n=n, c=1, rho=0.995)
                xbar = np.asarray(jnp.mean(xs, axis=0))

                def mc_mse(spec):
                    pipe = codec.as_pipeline(spec)
                    xhs = mc_estimates(pipe, xs, plan, trials=150,
                                        seed=200 + seed)
                    return float(np.mean(np.sum((xhs - xbar[None]) ** 2,
                                                axis=(1, 2))))

                mse_rk = mc_mse(codec.RandK(k=k, d_block=D))
                mse_sp = mc_mse(codec.SparseProj(k=k, d_block=D, s=8.0,
                                                 transform="avg"))
                # per-case: never worse than rand_k modulo MC slack
                assert mse_sp <= mse_rk * 1.05, (n, k, seed, mse_sp, mse_rk)
                ratios.append(mse_sp / mse_rk)
    # aggregate: the decode pays off, not just ties (observed mean ~0.7)
    assert np.mean(ratios) < 0.9, ratios


def test_sparse_proj_density_sweep_monotone_flops_bounded_variance():
    """Sparser maps (s up) must get STRICTLY cheaper to encode while the
    decode variance stays bounded: MSE at every density within 1.25x of the
    densest map's (observed <= 1.05x; the slack is MC noise, not physics)."""
    xs = client_chunks(0, c=1, rho=0.9)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    flops, mses = [], []
    for s in (1.0, 2.0, 4.0, 8.0, 16.0, 32.0):
        sp = codec.SparseProj(k=K, d_block=D, s=s, transform="avg")
        flops.append(sp.encode_flops_per_chunk())
        xhs = mc_estimates(codec.as_pipeline(sp), xs, None, trials=200,
                            seed=11)
        mses.append(float(np.mean(np.sum((xhs - xbar[None]) ** 2,
                                         axis=(1, 2)))))
    assert all(a > b for a, b in zip(flops, flops[1:])), flops
    assert max(mses) <= mses[0] * 1.25, list(zip(flops, mses))


def _sparse_proj_rounds(**cfg):
    """SparseProj's history over 3 fl.rounds under ``RoundConfig(**cfg)``."""
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds

    task = get_task("dme", n_clients=6, d=D, rho=0.9)
    pipe = codec.SparseProj(k=K, d_block=D, s=8.0, transform="avg")
    cohort = Cohort(n_clients=6, dropout=0.2)
    return run_rounds(task, pipe, cohort, RoundConfig(n_rounds=3, **cfg))[1]


@pytest.fixture(scope="module")
def sparse_proj_local_history():
    """The local-backend run every backend's case compares with."""
    return _sparse_proj_rounds()


@pytest.mark.parametrize("backend", ["local", "gspmd", "shard_map"])
def test_sparse_proj_backend_parity(backend, sparse_proj_local_history):
    """SparseProj through fl.rounds on all three backends: identical MSE
    trajectory and byte ledger (the estimator is backend-agnostic)."""
    h_ref = sparse_proj_local_history
    if backend == "local":
        h_cmp = h_ref
    else:
        mesh = make_mesh((jax.device_count(),), ("pod",))
        h_cmp = _sparse_proj_rounds(backend=backend, mesh=mesh)
    np.testing.assert_allclose(h_ref.mse, h_cmp.mse, rtol=1e-4, atol=1e-6)
    assert h_ref.bytes == h_cmp.bytes
