"""Seeded randomized property sweep (shared helpers: property_cases.py).

(f) **Adaptive per-chunk budgets** — the allocator conserves the total
budget exactly, the chunk_budgets decode stays unbiased at unchanged
wire bytes, and the composition gates reject what cannot compose.

(g) **Quantizer internals** — dither salts, the cohort context that
CorrelatedQuant needs, and its bit-exact re-derivation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from property_cases import C, D, K, N, RHO_D, RHO_N, client_chunks, mc_estimates
from repro.core import codec


# ------------------------------------------- (f) adaptive per-chunk budgets


@pytest.mark.parametrize("seed", range(20))
def test_adaptive_chunk_budgets_allocator_invariants(seed):
    """Randomized allocator sweep: the total C * k is conserved EXACTLY,
    every chunk stays in [1, d_block], and degenerate mass (zero, negative,
    non-finite) falls back to the uniform allocation."""
    rng = np.random.default_rng(seed)
    c = int(rng.integers(1, 9))
    d_block = int(rng.choice([8, 32, 64]))
    k = int(rng.integers(1, d_block + 1))
    mass = rng.uniform(0.0, 10.0, size=c) ** 4  # heavy-tailed mass
    got = codec.adaptive_chunk_budgets(mass, k, d_block)
    assert len(got) == c and sum(got) == c * k
    assert all(1 <= b <= d_block for b in got)
    # determinism: both wire ends derive the identical tuple
    assert got == codec.adaptive_chunk_budgets(mass, k, d_block)
    for bad in (np.zeros(c), -mass, np.full(c, np.nan)):
        assert codec.adaptive_chunk_budgets(bad, k, d_block) == (k,) * c


def test_adaptive_chunk_budgets_follow_mass():
    """Concentrated mass concentrates budget (clamped to d_block, the other
    chunks never go dark), proportional mass splits proportionally."""
    got = codec.adaptive_chunk_budgets([1.0, 0.0, 0.0, 0.0], k=8, d_block=64)
    assert got[0] == max(got) and got[0] > 8 and min(got) >= 1
    assert sum(got) == 32
    # clamp: one chunk can never exceed its dimension
    got = codec.adaptive_chunk_budgets([1.0, 0.0], k=16, d_block=16)
    assert got == (16, 16)
    got = codec.adaptive_chunk_budgets([3.0, 1.0], k=8, d_block=64)
    assert got == (12, 4)


def test_rand_k_chunk_budgets_unbiased_at_unchanged_bytes():
    """The chunk_budgets decode stays exactly unbiased at each chunk's own
    budget (decode scales chunk c by d/k_c), and the reallocation never
    changes the wire bytes (one flat row of sum(k_c) float32 values)."""
    pipe = codec.as_pipeline(codec.RandK(k=K, d_block=D,
                                         chunk_budgets=(K // 2, K + K // 2)))
    uniform = codec.as_pipeline(codec.RandK(k=K, d_block=D))
    assert pipe.payload_nbytes(C) == uniform.payload_nbytes(C)
    xs = client_chunks(9)
    payload = pipe.encode_payload(jax.random.key(0), 0, xs[0])
    assert codec.check_against_schema(payload) == []
    assert payload.nbytes == pipe.payload_nbytes(C)
    xhs = mc_estimates(pipe, xs, None, trials=200, seed=900)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    err = np.abs(xhs.mean(0) - xbar)
    sem = xhs.std(0) / np.sqrt(xhs.shape[0]) + 1e-4
    assert (err < 6 * sem + 5e-3).all(), float(err.max())


def test_chunk_budgets_validation_and_composition_gates():
    """chunk_budgets is rand_k-only, every entry lives in [1, d_block], the
    length must match the vector's chunk count, and the pipeline correctly
    declares itself non-streamable AND non-shardable."""
    with pytest.raises(ValueError, match="rand_k-only"):
        codec.RandKSpatial(k=K, d_block=D, chunk_budgets=(K, K))
    with pytest.raises(ValueError, match="chunk_budgets"):
        codec.RandK(k=K, d_block=D, chunk_budgets=(0, K))
    with pytest.raises(ValueError, match="chunk_budgets"):
        codec.RandK(k=K, d_block=D, chunk_budgets=(K, D + 1))
    sp = codec.RandK(k=K, d_block=D, chunk_budgets=(K, K, K))
    with pytest.raises(ValueError, match="3 entries"):
        sp.payload_schema(2)
    pipe = codec.as_pipeline(codec.RandK(k=K, d_block=D, chunk_budgets=(4, 12)))
    assert not pipe.chunk_streamable
    assert not pipe.decode_shardable
    assert pipe.non_streamable_stage[0] is pipe.sparsifier
    assert pipe.non_shardable_stage[0] is pipe.sparsifier


def test_adaptive_budget_rounds_reallocate_without_changing_ledger():
    """RoundConfig(adaptive_budgets=True) through fl.rounds: byte-identical
    ledger to the uniform run (pure reallocation), identical round 0 (no
    previous estimate -> uniform), diverging decode once the budget vector
    starts following the estimate's per-chunk mass."""
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds

    task = get_task("dme", n_clients=RHO_N, d=4 * RHO_D, rho=0.9)
    pipe = codec.RandK(k=K, d_block=RHO_D)
    cohort = Cohort(n_clients=RHO_N)
    _, h_uni = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=4))
    _, h_ada = run_rounds(task, pipe, cohort,
                          RoundConfig(n_rounds=4, adaptive_budgets=True))
    assert h_ada.bytes == h_uni.bytes
    assert h_ada.coded_bytes == h_uni.coded_bytes
    assert h_ada.mse[0] == h_uni.mse[0]
    assert h_ada.mse[1:] != h_uni.mse[1:]
    assert np.isfinite(h_ada.mse).all()


def test_adaptive_budget_rounds_config_gates():
    """The compositions the budget vector cannot survive are rejected up
    front, by name: non-rand_k sparsifiers, dist/hier backends, async
    rounds, overlap/ownership decodes."""
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds

    task = get_task("dme", n_clients=4, d=D, rho=0.9)
    cohort = Cohort(n_clients=4)
    rand_k = codec.RandK(k=K, d_block=D)
    cases = [
        (codec.TopK(k=K, d_block=D), dict(), "rewrites rand_k"),
        (rand_k, dict(backend="gspmd"), "backend='local'"),
        (rand_k, dict(async_rounds=True), "async"),
        (rand_k, dict(ownership=True, n_owners=2), "overlap/ownership"),
    ]
    for pipe, kw, match in cases:
        cfg = RoundConfig(n_rounds=1, adaptive_budgets=True, **kw)
        with pytest.raises(ValueError, match=match):
            run_rounds(task, pipe, cohort, cfg)


# ------------------------------------------------- (g) quantizer internals


def test_salt_mask_is_full_31_bits():
    """The dither-salt regression: the named legacy salts are pinned (wire
    bit-compat with the historical payload_dtype path), derived salts use
    the FULL 31-bit crc32 mask — 'acra' and 'acsh_v' collide under the old
    27-bit typo mask (0x7FFFFFF) and must not collide under the fix."""
    import zlib

    from repro.core.codec.quantizers import _SALTS, _salt

    for name, want in _SALTS.items():
        assert _salt(name) == want
    a, b = "acra", "acsh_v"
    assert (zlib.crc32(a.encode()) & 0x7FFFFFF) == \
           (zlib.crc32(b.encode()) & 0x7FFFFFF)  # the old mask collided them
    assert _salt(a) != _salt(b)
    for name in (a, b, "aux", "norm_sq"):
        assert _salt(name) == (zlib.crc32(name.encode()) & 0x7FFFFFFF)
        assert _salt(name) == _salt(name)  # deterministic


def test_correlated_quant_requires_cohort_context():
    """Encoding CorrelatedQuant outside the pipeline (no round key / client
    id) must raise instead of silently degenerating to independent
    rounding."""
    q = codec.CorrelatedQuant()
    arrays = {"vals": jnp.ones((C, K))}
    with pytest.raises(ValueError, match="round key"):
        q.encode(jax.random.key(0), arrays, ("vals",))


def test_correlated_quant_rederivation_is_bit_exact():
    """The re-derivation contract: a client's correlated encode is a pure
    function of (round_key, client_id) — the per-client encode_payload path
    must reproduce the vmapped encode_all bits exactly (this is what lets
    the rho tracker and the stale decode re-derive payloads server-side)."""
    from repro.core.codec.payload import arrays_of

    pipe = codec.Pipeline([codec.RandK(k=K, d_block=D),
                           codec.CorrelatedQuant()])
    xs = client_chunks(11)
    key = jax.random.key(5)
    stacked, _ = pipe.encode_all(key, xs)
    batch = arrays_of(stacked)
    for i in range(N):
        single = arrays_of(pipe.encode_payload(key, i, xs[i]))
        for name in batch:
            np.testing.assert_array_equal(np.asarray(batch[name][i]),
                                          np.asarray(single[name]), name)


def test_correlated_beats_int8_on_shared_support():
    """The cancellation claim, in miniature: on the identity sparsifier
    (full-vector DME — every client quantizes the same coordinate) the
    cohort-stratified dither beats independent stochastic rounding on
    mean-MSE at byte-identical payloads (observed ratio ~0.6; the full-size
    gate is benchmarks' extract-quant)."""
    d, n = 256, 8
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal((n, 1, d)), jnp.float32)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    mses = {}
    for q_name, q_ctor in (("int8", codec.Int8Quant),
                           ("correlated", codec.CorrelatedQuant)):
        pipe = codec.Pipeline([codec.Identity(d_block=d), q_ctor()])
        xhs = mc_estimates(pipe, xs, None, trials=64, seed=77)
        mses[q_name] = float(np.mean(np.sum((xhs - xbar[None]) ** 2,
                                            axis=(1, 2))))
    assert mses["correlated"] < 0.85 * mses["int8"], mses
    # byte parity: the win is not bought with a bigger payload
    p_int8 = codec.Pipeline([codec.Identity(d_block=d), codec.Int8Quant()])
    p_corr = codec.Pipeline([codec.Identity(d_block=d),
                             codec.CorrelatedQuant()])
    assert p_int8.payload_nbytes(1) == p_corr.payload_nbytes(1)
