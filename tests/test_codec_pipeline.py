"""The codec pipeline API (ISSUE 3): ledger honesty, stage-composition
unbiasedness, the legacy flat-keyword construction surface, true per-client
Rand-k-Temporal, and error feedback under heterogeneous budgets."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec
from repro.core.estimators import base as est_base
from repro.fl import Cohort, RoundConfig, get_task, run_rounds

jax.config.update("jax_platform_name", "cpu")

D, C = 64, 2  # d_block, chunks

ALL_SPARSIFIERS = [
    codec.RandK(k=8, d_block=D),
    codec.RandKSpatial(k=8, d_block=D, transform="avg"),
    codec.RandKSpatial(k=8, d_block=D, transform="avg", r_mode="est"),
    codec.RandProjSpatial(k=8, d_block=D, transform="avg"),
    codec.RandProjSpatial(k=8, d_block=D, transform="avg", r_mode="est"),
    codec.TopK(k=8, d_block=D),
    codec.Wangni(k=8, d_block=D),
    codec.Induced(k=8, d_block=D),
    codec.Identity(d_block=D),
]
QUANT_STAGES = [None, codec.Bf16Quant(), codec.Int8Quant()]


def _xs(n=6, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.standard_normal(D)
    xs = np.stack([shared + 0.3 * rng.standard_normal((C, D)) for _ in range(n)])
    return jnp.asarray(xs, jnp.float32)


# ------------------------------------------------------------ ledger honesty


@pytest.mark.parametrize("quant", QUANT_STAGES,
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("sp", ALL_SPARSIFIERS,
                         ids=lambda s: f"{s.name}{'-est' if getattr(s, 'r_mode', '') == 'est' else ''}")
def test_ledger_honesty_every_codec(sp, quant):
    """Payload.nbytes (actual array bytes) == meta.declared_nbytes (schema),
    for every registered sparsifier x quantizer combination — the declared
    ledger is computed from config alone, so drift (an uncounted int8 _scale
    array, a forgotten aux stat) cannot hide."""
    stages = [sp] + ([quant] if quant is not None else [])
    pipe = codec.Pipeline(stages)
    payload = pipe.encode_payload(jax.random.key(0), 3, _xs()[0])
    problems = codec.check_against_schema(payload)
    assert not problems, problems
    assert payload.nbytes == payload.meta.declared_nbytes
    assert payload.meta.declared_nbytes == pipe.payload_nbytes(C)
    # stacked payloads: per-client actual bytes still match the declaration
    stacked, _ = pipe.encode_all(jax.random.key(1), _xs())
    assert stacked.per_client_nbytes() == pipe.payload_nbytes(C)


def test_ledger_catches_undeclared_array():
    pipe = codec.Pipeline([codec.RandK(k=8, d_block=D)])
    payload = pipe.encode_payload(jax.random.key(0), 0, _xs()[0])
    payload.arrays["sneaky_scale"] = jnp.ones((C, 1))
    problems = codec.check_against_schema(payload)
    assert any("sneaky_scale" in p for p in problems)


def test_payload_meta_budget_rides_the_payload():
    pipe = codec.Pipeline([codec.RandK(k=8, d_block=D)])
    payload = pipe.encode_payload(jax.random.key(0), 0, _xs()[0])
    assert payload.meta.budget == 8 and payload.meta.d_block == D
    # a decoder configured at a DIFFERENT budget trusts the payload's meta
    other = codec.Pipeline([codec.RandK(k=16, d_block=D)])
    stacked, _ = pipe.encode_all(jax.random.key(1), _xs())
    a = other.decode_payload(jax.random.key(1), stacked, 6)
    b = pipe.decode_payload(jax.random.key(1), stacked, 6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ----------------------------------------------- composition unbiasedness


UNBIASED = [
    codec.RandK(k=8, d_block=D),
    codec.RandKSpatial(k=8, d_block=D, transform="avg"),
    codec.RandProjSpatial(k=8, d_block=D, transform="avg"),
    codec.Wangni(k=8, d_block=D),
    codec.Induced(k=8, d_block=D),
]


@pytest.mark.parametrize("with_side", [False, True], ids=["plain", "side_info"])
@pytest.mark.parametrize("sp", UNBIASED, ids=lambda s: s.name)
def test_pipeline_int8_composition_stays_unbiased(sp, with_side):
    """Property (ISSUE 3): Pipeline([<any unbiased sparsifier>, Int8Quant()])
    keeps E[decode] = mean(x), with and without temporal side information."""
    n = 6
    xs = _xs(n)
    pipe = codec.Pipeline([sp, codec.Int8Quant()])
    side = 0.5 * jnp.mean(xs, axis=0) if with_side else None
    xbar = np.asarray(jnp.mean(xs, axis=0))

    @jax.jit
    def one(key):
        return pipe.mean_estimate(key, xs, side_info=side)

    xhs = np.asarray(jax.lax.map(one, jax.random.split(jax.random.key(2), 600)))
    sem = xhs.std(0) / np.sqrt(len(xhs)) + 1e-4
    err = np.abs(xhs.mean(0) - xbar)
    assert (err < 6 * sem + 6e-3).all(), float(err.max())


# --------------------------------------------- legacy construction surface


def test_estimator_spec_is_gone():
    """The deprecated flat EstimatorSpec shim was removed: the class no
    longer exists anywhere on the public surface, and as_pipeline's error
    for spec-shaped strangers points at codec.build."""
    import repro.core
    import repro.core.estimators

    assert not hasattr(est_base, "EstimatorSpec")
    assert not hasattr(repro.core, "EstimatorSpec")
    assert not hasattr(repro.core.estimators, "EstimatorSpec")
    assert not hasattr(codec, "spec_to_pipeline")
    with pytest.raises(TypeError, match="expected Pipeline or sparsifier"):
        codec.as_pipeline(object())


def test_build_covers_old_flat_keywords():
    """codec.build is the keyword-compatible successor: the old flat spec
    fields (payload_dtype, ef, wangni_capacity, induced_topk_frac, renames)
    all land on the right typed stage configs."""
    pipe = codec.build("rand_proj_spatial", k=8, d_block=D,
                       payload_dtype="int8", ef=True)
    assert pipe.name == "rand_proj_spatial" and pipe.has_ef
    assert isinstance(pipe.quantizer, codec.Int8Quant)
    pw = codec.build("wangni", k=8, d_block=D, wangni_capacity=2.0)
    assert pw.sparsifier.capacity == 2.0
    pi = codec.build("induced", k=8, d_block=D, induced_topk_frac=0.25)
    assert pi.sparsifier.topk_frac == 0.25
    # first-party construction never warns (nothing deprecated left to trip)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        codec.build("rand_k", k=4, d_block=D)


def test_build_numeric_parity_with_explicit_pipeline():
    """build(...) and the hand-composed Pipeline produce IDENTICAL payloads
    and decodes for the same key (key derivation and int8 salts agree)."""
    xs = _xs()
    key = jax.random.key(5)
    for kw, stages in (
        (dict(), []),
        (dict(payload_dtype="int8"), [codec.Int8Quant()]),
        (dict(payload_dtype="bfloat16"), [codec.Bf16Quant()]),
    ):
        built = codec.build("rand_proj_spatial", k=8, d_block=D,
                            transform="avg", **kw)
        sp = codec.RandProjSpatial(k=8, d_block=D, transform="avg")
        pipe = codec.Pipeline([sp] + stages)
        a = built.mean_estimate(key, xs)
        b = pipe.mean_estimate(key, xs)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_build_rejects_unknown_fields_but_tolerates_legacy():
    with pytest.raises(TypeError, match="no field"):
        codec.build("rand_k", k=8, d_block=D, klingon=True)
    # legacy spec fields that do not apply are dropped (old flat behaviour)
    pipe = codec.build("rand_k", k=8, d_block=D, transform="one")
    assert pipe.transform is None


def test_pipeline_validation():
    with pytest.raises(ValueError, match="sparsifier"):
        codec.Pipeline([codec.Int8Quant()])
    with pytest.raises(ValueError, match="more than one"):
        codec.Pipeline([codec.RandK(k=4, d_block=D), codec.Identity(d_block=D)])
    with pytest.raises(TypeError):
        codec.Pipeline([codec.RandK(k=4, d_block=D), "not a stage"])


# ------------------------------------------- per-client temporal (satellite)


def test_per_client_temporal_beats_broadcast_on_drift():
    """ISSUE acceptance: true per-client Rand-k-Temporal (client-held
    memories in ClientState) beats the broadcast variant on a drifting task
    with persistent per-client offsets, at identical bytes."""
    task = get_task("drift", n_clients=8, d=2 * D, rho=0.95, omega=0.03,
                    client_bias=1.0)
    cohort = Cohort(n_clients=8)
    per_client = codec.Pipeline([codec.RandK(k=16, d_block=D), codec.Temporal()])
    broadcast = codec.RandK(k=16, d_block=D)
    _, h_pc = run_rounds(task, per_client, cohort, RoundConfig(n_rounds=30))
    _, h_bc = run_rounds(task, broadcast, cohort,
                         RoundConfig(n_rounds=30, temporal=True))
    assert h_pc.total_bytes == h_bc.total_bytes
    # compare after the per-client memories have warmed (eta = k/d per round)
    assert np.mean(h_pc.mse[15:]) < 0.7 * np.mean(h_bc.mse[15:])
    # the final client state carries the warmed memories
    assert h_pc.client_state is not None
    assert h_pc.client_state.memory.shape[0] == 8


def test_client_temporal_memory_tracks_clients():
    """Each client's memory converges toward ITS vector, not the mean."""
    task = get_task("drift", n_clients=4, d=D, rho=0.9, omega=0.0,
                    client_bias=1.0, seed=3)
    pipe = codec.Pipeline([codec.RandK(k=16, d_block=D), codec.Temporal()])
    _, hist = run_rounds(task, pipe, Cohort(n_clients=4),
                         RoundConfig(n_rounds=40))
    mem = np.asarray(hist.client_state.memory)[:, 0, :]  # (n, d)
    key = jax.random.fold_in(jax.random.key(0), 39)
    xs = np.asarray(task.client_vectors({"t": 39, "mean": None}, key))
    xbar = xs.mean(0)
    for i in range(4):
        d_own = np.linalg.norm(mem[i] - xs[i])
        d_mean = np.linalg.norm(mem[i] - xbar)
        assert d_own < d_mean, (i, d_own, d_mean)


def test_client_temporal_on_gspmd_matches_local():
    """Per-client temporal memories now ride the collectives backends
    (ROADMAP item): the server mirrors each surviving client's memory update
    by re-running the deterministic encode, so decode trajectory, byte
    ledger, AND the final memory state all match the local backend — under
    partial participation and dropout, where the scatter of partial cohorts
    back into the full state matters."""
    n, d = 6, 2 * D
    task = get_task("drift", n_clients=n, d=d, rho=0.9, omega=0.03,
                    client_bias=1.0)
    cohort = Cohort(n_clients=n, participation=0.9, dropout=0.2)
    pipe = codec.Pipeline([codec.RandK(k=16, d_block=D), codec.Temporal()])
    _, h_local = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=6))
    _, h_gspmd = run_rounds(task, pipe, cohort,
                            RoundConfig(n_rounds=6, backend="gspmd"))
    assert h_local.bytes == h_gspmd.bytes
    np.testing.assert_allclose(h_local.mse, h_gspmd.mse, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(h_local.client_state.memory),
        np.asarray(h_gspmd.client_state.memory), rtol=1e-4, atol=1e-6)


def test_client_temporal_on_shard_map_matches_local():
    """Same mirror on the shard_map backend."""
    n, d = 6, 2 * D
    task = get_task("drift", n_clients=n, d=d, rho=0.9, omega=0.03,
                    client_bias=1.0)
    cohort = Cohort(n_clients=n, dropout=0.2)
    pipe = codec.Pipeline([codec.RandK(k=16, d_block=D), codec.Temporal()])
    mesh = jax.make_mesh((1,), ("pod",))
    _, h_local = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=5))
    _, h_sm = run_rounds(task, pipe, cohort,
                         RoundConfig(n_rounds=5, backend="shard_map",
                                     mesh=mesh))
    assert h_local.bytes == h_sm.bytes
    np.testing.assert_allclose(h_local.mse, h_sm.mse, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(h_local.client_state.memory),
        np.asarray(h_sm.client_state.memory), rtol=1e-4, atol=1e-6)


# --------------------------------- EF x heterogeneous budgets (satellite)


def test_ef_with_heterogeneous_budgets_composes():
    """The old fl.rounds rejection is lifted: error feedback now operates per
    budget group (each client's residual follows its own k_i). Regression at
    two budget groups: runs, ledgers per-k_i, and on a gradient-descent task
    (where updates ACCUMULATE — the regime EF's guarantee is about) the EF
    run converges below the biased plain-Top-k run."""
    n, d = 6, D
    budgets = (8, 8, 8, 16, 16, 16)
    task = get_task("linear_regression", n_clients=n, d=d, samples=300)
    cohort = Cohort(n_clients=n, budgets=budgets)
    with_ef = codec.Pipeline([codec.TopK(k=8, d_block=d), codec.ErrorFeedback()])
    without = codec.TopK(k=8, d_block=d)
    _, h_ef = run_rounds(task, with_ef, cohort, RoundConfig(n_rounds=40))
    _, h_plain = run_rounds(task, without, cohort, RoundConfig(n_rounds=40))
    # ledger: every round, sum over clients of C * (k_i vals + k_i idx) * 4
    c = d // D
    want = sum(c * b * 8 for b in budgets)
    assert h_ef.bytes == [want] * 40 == h_plain.bytes
    # EF keeps flushing the mass plain Top-k silently drops
    assert np.mean(h_ef.metric[-10:]) < 0.8 * np.mean(h_plain.metric[-10:])
    # residual rows exist for every client at its own budget
    assert h_ef.client_state.ef.shape == (n, c, d)


def test_heterogeneous_budgets_on_gspmd_matches_local():
    """ISSUE acceptance: heterogeneous-budget cohorts decode on the gspmd
    backend, with per-client byte ledgers summing to the local totals."""
    n, d = 6, 2 * D
    task = get_task("dme", n_clients=n, d=d, rho=0.8)
    cohort = Cohort(n_clients=n, participation=1.0, dropout=0.2,
                    budgets=(8, 8, 16, 16, 32, 32))
    pipe = codec.RandProjSpatial(k=16, d_block=D, transform="avg",
                                 use_pallas="never")
    _, h_local = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=4))
    _, h_gspmd = run_rounds(task, pipe, cohort,
                            RoundConfig(n_rounds=4, backend="gspmd"))
    assert h_local.bytes == h_gspmd.bytes
    np.testing.assert_allclose(h_local.mse, h_gspmd.mse, rtol=1e-4, atol=1e-6)


def test_heterogeneous_budgets_on_shard_map_matches_local():
    """Budget groups loop over the shard_map collective too (ROADMAP item):
    ledger and decode parity with the local backend under dropout."""
    n, d = 6, 2 * D
    task = get_task("dme", n_clients=n, d=d, rho=0.8)
    cohort = Cohort(n_clients=n, budgets=(8, 8, 16, 16, 32, 32), dropout=0.2)
    pipe = codec.RandK(k=16, d_block=D)
    mesh = jax.make_mesh((1,), ("pod",))
    _, h_local = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=3))
    _, h_sm = run_rounds(task, pipe, cohort,
                         RoundConfig(n_rounds=3, backend="shard_map", mesh=mesh))
    assert h_local.bytes == h_sm.bytes
    np.testing.assert_allclose(h_local.mse, h_sm.mse, rtol=1e-4, atol=1e-6)


def test_ef_heterogeneous_budgets_on_gspmd():
    """EF + heterogeneous budgets compose on the collectives backend too."""
    n, d = 4, D
    task = get_task("dme", n_clients=n, d=d, rho=0.7)
    cohort = Cohort(n_clients=n, budgets=(8, 8, 16, 16))
    pipe = codec.Pipeline([codec.TopK(k=8, d_block=d), codec.ErrorFeedback()])
    _, h_local = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=5))
    _, h_gspmd = run_rounds(task, pipe, cohort,
                            RoundConfig(n_rounds=5, backend="gspmd"))
    np.testing.assert_allclose(h_local.mse, h_gspmd.mse, rtol=1e-4, atol=1e-6)
    assert h_local.bytes == h_gspmd.bytes


# ------------------------------------------------------- state mechanics


def test_client_state_is_a_pytree():
    st = codec.ClientState(ef=jnp.ones((4, 2, D)), memory=None)
    leaves = jax.tree.leaves(st)
    assert len(leaves) == 1 and leaves[0].shape == (4, 2, D)
    doubled = jax.tree.map(lambda a: 2 * a, st)
    assert isinstance(doubled, codec.ClientState)
    assert float(doubled.ef[0, 0, 0]) == 2.0 and doubled.memory is None


def test_ef_stage_residual_matches_collectives_buffer():
    """The ClientState EF path (fl.rounds local) and the raw ef_chunks buffer
    path (dist.collectives) implement the same residual recursion."""
    from repro.dist import collectives

    n, d, k = 4, D, 8
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal((n, 1, d)), jnp.float32)
    pipe = codec.Pipeline([codec.TopK(k=k, d_block=d), codec.ErrorFeedback()])
    # pipeline/state path
    st = pipe.init_client_state(n, 1)
    key = jax.random.key(7)
    _, st2 = pipe.encode_all(key, xs, states=st)
    # collectives/buffer path
    _, _, ef = collectives.compressed_mean_tree(pipe, key, {"x": xs[:, 0, :]})
    np.testing.assert_allclose(np.asarray(st2.ef), np.asarray(ef),
                               rtol=1e-6, atol=1e-6)


def test_shardmap_exchange_warns_when_it_falls_back():
    """A mesh without the client axis cannot host the shard_map exchange:
    the GSPMD path runs instead (same numbers), and a warning says so."""
    from repro.dist import collectives

    mesh = jax.make_mesh((1,), ("data",))
    pipe = codec.RandProjSpatial(k=8, d_block=D, transform="avg")
    grads = {"w": jax.random.normal(jax.random.key(0), (3, 2 * D))}
    with pytest.warns(RuntimeWarning, match="GSPMD"):
        mean, _, _ = collectives.compressed_mean_tree_shardmap(
            pipe, jax.random.key(1), grads, mesh)
    want, _, _ = collectives.compressed_mean_tree(pipe, jax.random.key(1), grads)
    np.testing.assert_array_equal(np.asarray(mean["w"]), np.asarray(want["w"]))
