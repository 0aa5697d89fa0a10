"""Sharded server decode (chunk ownership, docs/DESIGN.md §10), part 3:
``fl.rounds``.

Ownership decoding changes neither the MSE trajectory nor the transmitted
byte ledger on all three backends, and composes with heterogeneous budgets,
overlap, async rounds, error feedback and temporal decoding; cross-chunk
decode statistics are rejected. The plan and the decode core are in
``test_ownership.py``, tree-level ownership in ``test_ownership_tree.py``.
"""
from __future__ import annotations

import jax
import pytest

from ownership_cases import D, K
from repro.core import codec
from repro.fl import Cohort, RoundConfig, get_task, run_rounds
from repro.launch.mesh import make_mesh


# ------------------------------------------------------------------ fl rounds


@pytest.mark.parametrize("backend", ["local", "gspmd", "shard_map"])
def test_rounds_ownership_parity(backend):
    """The fl acceptance: ownership decoding changes neither the MSE
    trajectory nor the transmitted-byte ledger on any backend."""
    task = get_task("drift", n_clients=8, d=D, rho=0.95, omega=0.02)
    pipe = codec.RandProjSpatial(k=K, d_block=D, transform="avg")
    cohort = Cohort(n_clients=8, dropout=0.2)
    mesh = None if backend == "local" else make_mesh(
        (jax.device_count(),), ("pod",))
    base = dict(n_rounds=4, backend=backend, mesh=mesh)
    _, h0 = run_rounds(task, pipe, cohort, RoundConfig(**base))
    _, h1 = run_rounds(task, pipe, cohort,
                       RoundConfig(**base, ownership=True, n_owners=4))
    assert h0.mse == h1.mse
    assert h0.bytes == h1.bytes
    # the ownership run ledgers its modelled intra-pod traffic per round
    assert len(h1.intra_pod_bytes) == 4
    if backend == "local":
        assert all(b > 0 for b in h1.intra_pod_bytes)
        assert all(b == 0 for b in h0.intra_pod_bytes)


def test_rounds_ownership_heterogeneous_budgets():
    """Owners see mixed per-client k_i: budget groups decode independently
    through the sharded path, and the trajectory matches the unsharded one."""
    budgets = (8, 8, 8, 32, 32, 32, 16, 16)
    task = get_task("drift", n_clients=8, d=D, rho=0.95, omega=0.02)
    pipe = codec.RandK(k=K, d_block=D)
    cohort = Cohort(n_clients=8, dropout=0.2, budgets=budgets)
    _, h0 = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=4))
    _, h1 = run_rounds(task, pipe, cohort,
                       RoundConfig(n_rounds=4, ownership=True, n_owners=2))
    assert h0.mse == h1.mse
    assert h0.bytes == h1.bytes


def test_rounds_ownership_composes_with_overlap_and_async():
    task = get_task("drift", n_clients=8, d=D, rho=0.95, omega=0.02)
    pipe = codec.RandProjSpatial(k=K, d_block=D, transform="avg")
    cohort = Cohort(n_clients=8, dropout=0.3)
    base = dict(n_rounds=5)
    _, h0 = run_rounds(task, pipe, cohort, RoundConfig(**base))
    _, h1 = run_rounds(task, pipe, cohort, RoundConfig(
        **base, ownership=True, n_owners=3, overlap=True, overlap_tile=2))
    assert h0.mse == h1.mse
    _, h2 = run_rounds(task, pipe, cohort, RoundConfig(**base,
                                                       async_rounds=True))
    _, h3 = run_rounds(task, pipe, cohort, RoundConfig(
        **base, async_rounds=True, ownership=True, n_owners=3))
    assert h2.mse == h3.mse and h2.bytes == h3.bytes
    assert sum(h3.n_stale) == sum(h2.n_stale)


def test_rounds_ownership_composes_with_ef_and_temporal():
    task = get_task("drift", n_clients=6, d=D, rho=0.95, omega=0.02,
                    client_bias=0.5)
    cohort = Cohort(n_clients=6, dropout=0.2)
    for stages in ([codec.RandK(k=K, d_block=D), codec.ErrorFeedback()],
                   [codec.RandK(k=K, d_block=D), codec.Temporal()]):
        pipe = codec.Pipeline(stages)
        _, h0 = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=4))
        _, h1 = run_rounds(task, pipe, cohort,
                           RoundConfig(n_rounds=4, ownership=True, n_owners=3))
        assert h0.mse == h1.mse


def test_rounds_ownership_rejects_cross_chunk_decode():
    task = get_task("dme", n_clients=4, d=D, rho=0.9)
    pipe = codec.RandKSpatial(k=K, d_block=D, transform="avg", r_mode="est")
    with pytest.raises(ValueError, match="RandKSpatial"):
        run_rounds(task, pipe, cfg=RoundConfig(n_rounds=1, ownership=True,
                                               n_owners=2))
