"""Async execution layer, part 2: staleness-1 FL rounds (overlapped
collectives are in ``test_async_overlap.py``).

Two claims are pinned here:

1. **Staleness-1 admission** — with ``dropout=0`` the async driver equals
   the sync one exactly; with stragglers, admitting their late payloads
   (a) improves population MSE vs dropping them and (b) costs exactly the
   admitted payloads' declared bytes (ledger identity).
2. **Staleness metadata** — ``codec.with_staleness`` tags a payload without
   touching arrays or wire bytes, so the ledger-honesty check and the
   decode are unchanged for stale payloads.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core import codec
from repro.fl import Cohort, RoundConfig, get_task, run_rounds

D = 128
K = 16


# ---------------------------------------------------------------- async rounds


def test_async_equals_sync_without_stragglers():
    """dropout=0: the stale buffer never fills, so the async driver's whole
    History matches the sync driver's exactly."""
    task = get_task("drift", n_clients=8, d=D, rho=0.95, omega=0.02)
    pipe = codec.RandProjSpatial(k=K, d_block=D, transform="avg")
    _, h_sync = run_rounds(task, pipe, cfg=RoundConfig(n_rounds=5))
    _, h_async = run_rounds(task, pipe,
                            cfg=RoundConfig(n_rounds=5, async_rounds=True))
    assert h_sync.mse == h_async.mse
    assert h_sync.mse_pop == h_async.mse_pop
    assert h_sync.bytes == h_async.bytes
    assert sum(h_async.n_stale) == 0


def test_async_ledger_identity_and_staleness0_ablation():
    """Every late ARRIVAL is ledgered at its declared bytes (admitted into
    the decode or superseded by a fresh report — it crossed the wire either
    way), and staleness=0 (async scheduling, no admission) decodes
    identically to sync — the byte-ledger parity of the acceptance
    criteria."""
    task = get_task("drift", n_clients=8, d=D, rho=0.95, omega=0.02)
    pipe = codec.RandProjSpatial(k=K, d_block=D, transform="avg")
    cohort = Cohort(n_clients=8, dropout=0.3)
    _, h_sync = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=10))
    _, h_async = run_rounds(task, pipe, cohort,
                            RoundConfig(n_rounds=10, async_rounds=True))
    _, h_drop = run_rounds(
        task, pipe, cohort,
        RoundConfig(n_rounds=10, async_rounds=True, staleness=0))
    assert sum(h_async.n_stale) > 0
    assert h_async.total_bytes == h_sync.total_bytes + h_async.total_stale_bytes
    per_round = [s + extra for s, extra in zip(h_sync.bytes,
                                               h_async.stale_bytes)]
    assert h_async.bytes == per_round
    assert h_drop.mse == h_sync.mse  # no admission => sync decode exactly


def test_straggler_admission_improves_population_mse():
    """The tentpole claim: a late payload admitted at staleness 1 beats
    dropping it — population MSE (vs ALL clients' current mean) improves on
    a slowly-drifting correlated task."""
    task = get_task("drift", n_clients=8, d=256, rho=0.95, omega=0.02)
    pipe = codec.RandProjSpatial(k=26, d_block=256, transform="avg")
    cohort = Cohort(n_clients=8, dropout=0.3)
    _, h_sync = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=25))
    _, h_async = run_rounds(task, pipe, cohort,
                            RoundConfig(n_rounds=25, async_rounds=True))
    assert sum(h_async.n_stale) > 0
    assert np.mean(h_async.mse_pop) < np.mean(h_sync.mse_pop)


def test_async_composes_with_per_client_temporal():
    """Stragglers' temporal memories advance when they (late-)encode, and
    the stale decode adds back the snapshot they actually encoded against."""
    task = get_task("drift", n_clients=6, d=D, rho=0.95, omega=0.02,
                    client_bias=0.5)
    pipe = codec.Pipeline([codec.RandK(k=K, d_block=D), codec.Temporal()])
    cohort = Cohort(n_clients=6, dropout=0.3)
    _, hist = run_rounds(task, pipe, cohort,
                         RoundConfig(n_rounds=8, async_rounds=True))
    assert sum(hist.n_stale) > 0
    assert hist.client_state is not None
    assert np.isfinite(hist.mse_pop).all()


def test_async_rejects_error_feedback_and_deep_staleness():
    task = get_task("dme", n_clients=4, d=D, rho=0.9)
    pipe_ef = codec.Pipeline([codec.RandK(k=K, d_block=D),
                              codec.ErrorFeedback()])
    with pytest.raises(ValueError, match="[Ee]rror feedback"):
        run_rounds(task, pipe_ef, cfg=RoundConfig(n_rounds=1,
                                                  async_rounds=True))
    pipe = codec.RandK(k=K, d_block=D)
    with pytest.raises(ValueError, match="staleness"):
        run_rounds(task, pipe, cfg=RoundConfig(n_rounds=1, async_rounds=True,
                                               staleness=2))


# ---------------------------------------------------------- staleness metadata


def test_with_staleness_pure_metadata(rng_key):
    """The staleness tag changes neither arrays nor the declared ledger:
    stale payloads pass the same honesty check and decode to the same
    numbers (it is the decode's round KEY that differs for a stale payload,
    never its bytes)."""
    pipe = codec.as_pipeline(
        codec.Pipeline([codec.RandProjSpatial(k=K, d_block=D),
                        codec.Bf16Quant()]))
    x = jax.random.normal(jax.random.fold_in(rng_key, 7), (4, D))
    payload = pipe.encode_payload(rng_key, 0, x)
    assert payload.meta.staleness == 0
    stale = codec.with_staleness(payload, 1)
    assert stale.meta.staleness == 1
    assert payload.meta.staleness == 0  # original untouched
    assert codec.check_against_schema(stale) == []
    assert stale.nbytes == payload.nbytes
    assert stale.meta.declared_nbytes == payload.meta.declared_nbytes
    np.testing.assert_array_equal(
        np.asarray(pipe.self_decode(rng_key, 0, stale)),
        np.asarray(pipe.self_decode(rng_key, 0, payload)))

    with pytest.raises(ValueError, match="staleness"):
        codec.with_staleness(payload, -1)
    with pytest.raises(TypeError):
        codec.with_staleness({"vals": x}, 1)


def test_stale_stacked_payload_ledger(rng_key):
    """Ledger honesty extends to stale STACKED payloads: per-client bytes
    read off the schema are unchanged by the tag (what fl.rounds charges an
    admitted payload)."""
    pipe = codec.as_pipeline(codec.RandK(k=K, d_block=D))
    xs = jax.random.normal(rng_key, (5, 3, D))
    payloads, _ = pipe.encode_all(rng_key, xs)
    stale = codec.with_staleness(payloads, 1)
    assert stale.per_client_nbytes() == payloads.per_client_nbytes()
    assert stale.per_client_nbytes() == pipe.payload_nbytes(3)
    np.testing.assert_array_equal(
        np.asarray(pipe.decode_payload(rng_key, stale, 5)),
        np.asarray(pipe.decode_payload(rng_key, payloads, 5)))
