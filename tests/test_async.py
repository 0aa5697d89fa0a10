"""Async execution layer: overlapped collectives, staleness-1 FL rounds.

Three claims are pinned here:

1. **Overlap parity** — ``overlap=True`` (double-buffered chunk streaming)
   is BIT-identical to the synchronous path: through both dist entry points
   (with participants and error feedback) and through ``fl.rounds`` on all
   three backends. Non-streamable pipelines are rejected, never silently
   degraded.
2. **Staleness-1 admission** — with ``dropout=0`` the async driver equals
   the sync one exactly; with stragglers, admitting their late payloads
   (a) improves population MSE vs dropping them and (b) costs exactly the
   admitted payloads' declared bytes (ledger identity).
3. **Staleness metadata** — ``codec.with_staleness`` tags a payload without
   touching arrays or wire bytes, so the ledger-honesty check and the
   decode are unchanged for stale payloads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec
from repro.dist import collectives
from repro.fl import Cohort, RoundConfig, get_task, run_rounds
from repro.launch.mesh import make_mesh

D = 128
K = 16


def _tree(np_rng, n=6):
    return {
        "w": jnp.asarray(np_rng.standard_normal((n, 40, 20)), jnp.float32),
        "b": jnp.asarray(np_rng.standard_normal((n, 33)), jnp.float32),
    }


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


STREAMABLE = [
    codec.RandK(k=K, d_block=D),
    codec.RandKSpatial(k=K, d_block=D, transform="avg"),
    codec.RandProjSpatial(k=K, d_block=D, transform="avg"),
    codec.TopK(k=K, d_block=D),
    codec.Identity(d_block=D),
    codec.Pipeline([codec.RandProjSpatial(k=K, d_block=D), codec.Bf16Quant()]),
    codec.Pipeline([codec.RandK(k=K, d_block=D), codec.ErrorFeedback()]),
]


@pytest.mark.parametrize("spec", STREAMABLE, ids=lambda s: codec.as_pipeline(s).describe())
def test_overlap_bitwise_parity_gspmd(spec, rng_key, np_rng):
    tree = _tree(np_rng)
    pipe = codec.as_pipeline(spec)
    m0, i0, e0 = collectives.compressed_mean_tree(pipe, rng_key, tree)
    m1, i1, e1 = collectives.compressed_mean_tree(pipe, rng_key, tree,
                                                  overlap=True)
    _assert_trees_equal(m0, m1)
    assert i0 == i1
    if e0 is not None:
        np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


def test_overlap_parity_with_participants_and_tile(rng_key, np_rng):
    tree = _tree(np_rng)
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=K, d_block=D))
    part = [0, 2, 5]
    m0, i0, _ = collectives.compressed_mean_tree(
        pipe, rng_key, tree, participants=part)
    for tile in (1, 3):
        m1, i1, _ = collectives.compressed_mean_tree(
            pipe, rng_key, tree, participants=part, overlap=True,
            overlap_tile=tile)
        _assert_trees_equal(m0, m1)
        assert i0 == i1


def test_overlap_parity_shardmap(rng_key, np_rng):
    tree = _tree(np_rng)
    mesh = jax.make_mesh((jax.device_count(),), ("pod",))
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=K, d_block=D))
    m0, i0, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh)
    m1, i1, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh, overlap=True)
    _assert_trees_equal(m0, m1)
    assert i0 == i1


def test_overlap_edge_tiles_ragged_and_oversized(rng_key, np_rng):
    """Edge-tile coverage: the 7-chunk grid under a tile that does NOT
    divide it (ragged final tile), a tile larger than the whole grid, and a
    tile equal to it — all bit-identical to the sync decode."""
    tree = _tree(np_rng)
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=K, d_block=D))
    m0, i0, _ = collectives.compressed_mean_tree(pipe, rng_key, tree)
    n_chunks = i0["n_chunks"]
    assert n_chunks == 7  # the fixture's d_flat=833 over d_block=128
    for tile in (2, 4, 6, n_chunks, n_chunks + 5, 64):
        m1, i1, _ = collectives.compressed_mean_tree(
            pipe, rng_key, tree, overlap=True, overlap_tile=tile)
        _assert_trees_equal(m0, m1)
        assert i0 == i1
    # tile geometry itself: ragged final tile and single oversized tile
    assert collectives.stream_tiles(7, 4) == [(0, 4), (4, 7)]
    assert collectives.stream_tiles(7, 64) == [(0, 7)]
    with pytest.raises(ValueError, match="overlap_tile"):
        collectives.stream_tiles(7, 0)


def test_overlap_edge_tiles_under_ownership(rng_key, np_rng):
    """Ragged tiles x ragged ownership: tiles are owner-local (never span an
    owner boundary) and still reproduce the sync decode bit-for-bit,
    including with error feedback riding along."""
    from repro.dist.sharding import chunk_ownership

    tree = _tree(np_rng)
    plan = chunk_ownership(7, 3)  # slices (0,3) (3,6) (6,7): ragged tail
    assert collectives.stream_tiles(7, 2, plan) == [
        (0, 2), (2, 3), (3, 5), (5, 6), (6, 7)]
    assert collectives.stream_tiles(7, 64, plan) == [(0, 3), (3, 6), (6, 7)]
    for spec in (codec.RandProjSpatial(k=K, d_block=D),
                 codec.Pipeline([codec.RandK(k=K, d_block=D),
                                 codec.ErrorFeedback()])):
        pipe = codec.as_pipeline(spec)
        m0, _, e0 = collectives.compressed_mean_tree(pipe, rng_key, tree)
        for tile in (2, 3, 64):
            m1, _, e1 = collectives.compressed_mean_tree(
                pipe, rng_key, tree, ownership=plan, overlap=True,
                overlap_tile=tile)
            _assert_trees_equal(m0, m1)
            if e0 is not None:
                np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


NON_STREAMABLE = [
    codec.Pipeline([codec.RandK(k=K, d_block=D), codec.Int8Quant()]),
    codec.RandK(k=K, d_block=D, shared_randomness=False),
    codec.Wangni(k=K, d_block=D),
    codec.Induced(k=K, d_block=D),
    codec.SparseProj(k=K, d_block=D, shared_randomness=False),
]


@pytest.mark.parametrize("spec", NON_STREAMABLE,
                         ids=lambda s: codec.as_pipeline(s).describe())
def test_overlap_rejects_non_streamable(spec, rng_key, np_rng):
    assert not codec.as_pipeline(spec).chunk_streamable
    with pytest.raises(ValueError, match="chunk-streamable"):
        collectives.compressed_mean_tree(spec, rng_key, _tree(np_rng),
                                         overlap=True)


@pytest.mark.parametrize("spec,offender", [
    (codec.Pipeline([codec.RandK(k=K, d_block=D), codec.Int8Quant()]),
     "Int8Quant"),
    (codec.RandK(k=K, d_block=D, shared_randomness=False), "RandK"),
    (codec.Wangni(k=K, d_block=D), "Wangni"),
    (codec.Induced(k=K, d_block=D), "Induced"),
    (codec.SparseProj(k=K, d_block=D, shared_randomness=False), "SparseProj"),
])
def test_check_streamable_names_offending_stage(spec, offender):
    """The rejection must NAME the stage class that breaks streamability and
    say why, not just reject generically."""
    pipe = codec.as_pipeline(spec)
    with pytest.raises(ValueError) as ei:
        collectives.check_streamable(pipe)
    msg = str(ei.value)
    assert offender in msg, msg
    assert "overlap=False" in msg  # tells the caller the way out
    if offender == "Int8Quant":
        assert "rounding noise" in msg
    else:
        assert "position" in msg


@pytest.mark.parametrize("backend", ["local", "gspmd", "shard_map"])
def test_overlap_parity_through_rounds(backend):
    """The satellite acceptance: overlap=True is bit-identical to the sync
    decode on all three fl backends (MSE and ledger, whole trajectory)."""
    task = get_task("drift", n_clients=8, d=D, rho=0.95, omega=0.02)
    pipe = codec.RandProjSpatial(k=K, d_block=D, transform="avg")
    cohort = Cohort(n_clients=8, dropout=0.2)
    mesh = None if backend == "local" else make_mesh(
        (jax.device_count(),), ("pod",))
    base = dict(n_rounds=4, backend=backend, mesh=mesh)
    _, h0 = run_rounds(task, pipe, cohort, RoundConfig(**base))
    _, h1 = run_rounds(task, pipe, cohort, RoundConfig(**base, overlap=True))
    assert h0.mse == h1.mse
    assert h0.bytes == h1.bytes


def test_overlap_requires_stateless_pipeline():
    task = get_task("dme", n_clients=4, d=D, rho=0.9)
    stateful = codec.Pipeline([codec.RandK(k=K, d_block=D),
                               codec.ErrorFeedback()])
    with pytest.raises(ValueError, match="stateless"):
        run_rounds(task, stateful, cfg=RoundConfig(n_rounds=1, overlap=True))


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
