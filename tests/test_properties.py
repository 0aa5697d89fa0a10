"""Seeded randomized property sweeps (no third-party property-test dep).

Three invariant families, each swept over parametrized grids (>= 200 cases
total) with deterministic per-case seeds, and each run BOTH through the
monolithic decode and the new chunk-ownership sharded decode
(docs/DESIGN.md §10) — the ownership path must preserve every invariant:

(a) **Unbiasedness** — E[decode] ≈ true mean for every registered unbiased
    sparsifier x quantizer pipeline (top_k is biased by construction and
    pairs with ErrorFeedback instead; bf16's deterministic rounding gets a
    rounding-sized slack on top of the Monte-Carlo tolerance).
(b) **Lemma 4.1-style variance ordering** — at rho -> 1,
    MSE(rand_proj_spatial) <= MSE(rand_k_spatial) <= MSE(rand_k): the
    correlation-aware decoders strictly pay off where correlation exists.
(c) **Ledger honesty** — under RANDOM budgets and participant sets, the
    declared byte ledger equals the actual array bytes, ``bytes_sent``
    charges exactly the survivors, and the intra-pod columns are
    internally consistent.
(d) **Rho-tracker calibration** — ``fl.server.measure_rho`` on known-rho
    cohorts lands within tolerance of the true rho and NEVER overclaims,
    for every self-decodable sparsifier (sparse_proj at several densities —
    the per-codec ``self_decode_norm_inflation`` regression) x quantizer.
(e) **Entropy-coded wire honesty** — ``EntropyCode``'s declared coded size
    equals the length of the byte stream it actually emits, and the stream
    round-trips bit-exactly, per sparsifier x quantizer.
(f) **Adaptive per-chunk budgets** — the allocator conserves the total
    budget exactly, the chunk_budgets decode stays unbiased at unchanged
    wire bytes, and the composition gates reject what cannot compose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec
from repro.dist import collectives
from repro.dist.sharding import chunk_ownership
from repro.launch.mesh import make_mesh

D = 64
C = 2
N = 6
K = 8

# (name, sparsifier ctor) — the unbiased family (top_k excluded: biased)
UNBIASED_SPARSIFIERS = [
    ("rand_k", lambda: codec.RandK(k=K, d_block=D)),
    ("rand_k_spatial", lambda: codec.RandKSpatial(k=K, d_block=D,
                                                  transform="avg")),
    ("rand_proj_spatial", lambda: codec.RandProjSpatial(k=K, d_block=D,
                                                        transform="avg")),
    ("wangni", lambda: codec.Wangni(k=K, d_block=D)),
    ("induced", lambda: codec.Induced(k=K, d_block=D)),
    ("identity", lambda: codec.Identity(d_block=D)),
    ("sparse_proj", lambda: codec.SparseProj(k=K, d_block=D, s=8.0,
                                             transform="avg")),
]

QUANTIZERS = [
    ("none", None),
    ("bf16", codec.Bf16Quant),
    ("int8", codec.Int8Quant),
    ("correlated", codec.CorrelatedQuant),
]


def _pipeline(sp_ctor, q_ctor):
    stages = [sp_ctor()]
    if q_ctor is not None:
        stages.append(q_ctor())
    return codec.Pipeline(stages)


def _clients(seed, n=N, c=C, d=D, rho=None):
    """(n, c, d) client chunks; ``rho`` close to 1 => near-identical rows."""
    rng = np.random.default_rng(seed)
    if rho is None:
        xs = rng.standard_normal((n, c, d))
    else:
        base = rng.standard_normal((c, d))
        noise = rng.standard_normal((n, c, d))
        xs = rho * base[None] + np.sqrt(max(0.0, 1 - rho**2)) * noise
    xs = xs / np.linalg.norm(xs, axis=-1, keepdims=True)
    return jnp.asarray(xs, jnp.float32)


def _mc_estimates(pipe, xs, plan, trials, seed):
    """(trials, C, d) decodes under independent round keys; the decode runs
    owner-partitioned when ``plan`` is given."""
    n = xs.shape[0]

    @jax.jit
    def one(key):
        payloads, _ = pipe.encode_all(key, xs)
        if plan is None:
            return pipe.decode_payload(key, payloads, n)
        return collectives.sharded_decode(pipe, key, payloads, n, plan)

    keys = jax.random.split(jax.random.key(seed), trials)
    return np.asarray(jax.lax.map(one, keys))


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
    pipe = _pipeline(sp_ctor, q_ctor)
    xs = _clients(seed)
    plan = chunk_ownership(C, 2) if ownership else None
    xhs = _mc_estimates(pipe, xs, plan, trials=160, seed=100 + seed)
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
    xs = _clients(seed, rho=0.9)
    plan = chunk_ownership(C, 2) if ownership else None
    xhs = _mc_estimates(pipe, xs, plan, trials=160, seed=500 + seed)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    err = np.abs(xhs.mean(0) - xbar)
    sem = xhs.std(0) / np.sqrt(xhs.shape[0]) + 1e-4
    assert (err < 6 * sem + 5e-3).all(), (projection, float(err.max()))


def test_top_k_is_biased_hence_excluded():
    """The counter-property: top_k's E[decode] != mean (that is WHY it pairs
    with ErrorFeedback and sits outside the unbiased sweep)."""
    pipe = codec.as_pipeline(codec.TopK(k=4, d_block=D))
    xs = _clients(3)
    xhs = _mc_estimates(pipe, xs, None, trials=160, seed=3)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    err = np.abs(xhs.mean(0) - xbar)
    sem = xhs.std(0) / np.sqrt(xhs.shape[0]) + 1e-4
    assert (err > 6 * sem + 5e-3).any()


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
    xs = _clients(seed, n=n, c=1, rho=0.995)
    plan = chunk_ownership(1, 2) if ownership else None
    xbar = np.asarray(jnp.mean(xs, axis=0))

    def mc_mse(spec):
        pipe = codec.as_pipeline(spec)
        xhs = _mc_estimates(pipe, xs, plan, trials=150, seed=200 + seed)
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
                xs = _clients(seed, n=n, c=1, rho=0.995)
                xbar = np.asarray(jnp.mean(xs, axis=0))

                def mc_mse(spec):
                    pipe = codec.as_pipeline(spec)
                    xhs = _mc_estimates(pipe, xs, plan, trials=150,
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
    xs = _clients(0, c=1, rho=0.9)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    flops, mses = [], []
    for s in (1.0, 2.0, 4.0, 8.0, 16.0, 32.0):
        sp = codec.SparseProj(k=K, d_block=D, s=s, transform="avg")
        flops.append(sp.encode_flops_per_chunk())
        xhs = _mc_estimates(codec.as_pipeline(sp), xs, None, trials=200,
                            seed=11)
        mses.append(float(np.mean(np.sum((xhs - xbar[None]) ** 2,
                                         axis=(1, 2)))))
    assert all(a > b for a, b in zip(flops, flops[1:])), flops
    assert max(mses) <= mses[0] * 1.25, list(zip(flops, mses))


@pytest.mark.parametrize("backend", ["local", "gspmd", "shard_map"])
def test_sparse_proj_backend_parity(backend):
    """SparseProj through fl.rounds on all three backends: identical MSE
    trajectory and byte ledger (the estimator is backend-agnostic)."""
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds

    task = get_task("dme", n_clients=6, d=D, rho=0.9)
    pipe = codec.SparseProj(k=K, d_block=D, s=8.0, transform="avg")
    cohort = Cohort(n_clients=6, dropout=0.2)
    _, h_ref = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=3))
    if backend == "local":
        h_cmp = h_ref
    else:
        mesh = make_mesh((jax.device_count(),), ("pod",))
        _, h_cmp = run_rounds(task, pipe, cohort,
                              RoundConfig(n_rounds=3, backend=backend,
                                          mesh=mesh))
    np.testing.assert_allclose(h_ref.mse, h_cmp.mse, rtol=1e-4, atol=1e-6)
    assert h_ref.bytes == h_cmp.bytes


# ------------------------------------------------------------ (c) ledger honesty


LEDGER_SPARSIFIERS = ["rand_k", "rand_k_spatial", "top_k", "wangni",
                      "induced", "identity"]


@pytest.mark.parametrize("ownership", [False, True],
                         ids=["monolithic", "ownership"])
@pytest.mark.parametrize("seed", range(60))
def test_ledger_honesty_random_budgets_participants(seed, ownership):
    """120 randomized cases: random sparsifier/quantizer/budget/participant
    draws; the declared schema must equal the actual payload bytes, the
    collectives ledger must charge exactly the survivors, and the intra-pod
    columns must be internally consistent."""
    rng = np.random.default_rng(seed)
    name = LEDGER_SPARSIFIERS[rng.integers(len(LEDGER_SPARSIFIERS))]
    d_block = int(rng.choice([32, 64, 128]))
    # wangni's fixed-capacity packing needs capacity_slots <= d_block
    k_hi = d_block // 2 if name == "wangni" else d_block
    k = int(rng.integers(1, k_hi + 1))
    q_name, q_ctor = QUANTIZERS[rng.integers(len(QUANTIZERS))]
    kw = {"transform": "avg"} if name == "rand_k_spatial" else {}
    if name == "identity":
        stages = [codec.Identity(d_block=d_block)]
    else:
        stages = [codec.SPARSIFIERS[name](k=k, d_block=d_block, **kw)]
    if q_ctor is not None:
        stages.append(q_ctor())
    pipe = codec.Pipeline(stages)

    n_total = int(rng.integers(2, 9))
    n_part = int(rng.integers(1, n_total + 1))
    if name == "rand_k_spatial" and n_part == 1:
        # the avg/opt interpolations are undefined at n=1 (rho = R/(n-1));
        # fl.server.resolve_pipeline rewrites to "one" — mirror it here
        stages[0] = stages[0].replace(transform="one")
        pipe = codec.Pipeline(stages)
    participants = np.sort(rng.choice(n_total, n_part, replace=False))
    d_flat = int(rng.integers(d_block, 4 * d_block + 1))
    tree = {"x": jnp.asarray(rng.standard_normal((n_total, d_flat)),
                             jnp.float32)}
    n_owners = int(rng.integers(2, 5)) if ownership else None

    key = jax.random.key(seed)
    _, info, _ = collectives.compressed_mean_tree(
        pipe, key, tree, participants=participants,
        ownership=n_owners,
    )

    # declared ledger == actual payload bytes for a real encode
    payload = pipe.encode_payload(key, 0, jnp.zeros((info["n_chunks"], d_block)))
    assert codec.check_against_schema(payload) == []
    assert payload.nbytes == pipe.payload_nbytes(info["n_chunks"])

    # the collectives ledger charges exactly the survivors
    assert info["n_clients"] == n_part
    assert info["n_total"] == n_total
    assert info["bytes_sent"] == n_part * pipe.payload_nbytes(info["n_chunks"])

    # intra-pod columns: the taken route's column is THE column, and the
    # standalone model reproduces the info dict exactly
    if ownership:
        assert info["n_shards"] == n_owners
        assert info["intra_pod_bytes"] == info["intra_pod_bytes_ownership"]
        model = collectives.intra_pod_traffic(
            pipe, n_part, info["n_chunks"], n_owners,
            plan=chunk_ownership(info["n_chunks"], n_owners))
        assert model == {k: info[k] for k in model}
    else:
        assert info["intra_pod_bytes"] == 0  # single logical shard


@pytest.mark.parametrize("seed", range(12))
def test_ledger_honesty_heterogeneous_budget_rounds(seed):
    """Randomized budget-group cohorts through fl.rounds: the per-round byte
    ledger equals the sum of each group's declared payload bytes, with and
    without ownership (24 cases)."""
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds

    rng = np.random.default_rng(1000 + seed)
    n_clients = int(rng.integers(4, 9))
    budgets = tuple(int(rng.choice([4, 8, 16])) for _ in range(n_clients))
    task = get_task("dme", n_clients=n_clients, d=D, rho=0.9, seed=seed)
    pipe = codec.RandK(k=8, d_block=D)
    cohort = Cohort(n_clients=n_clients, dropout=float(rng.uniform(0, 0.4)),
                    budgets=budgets)
    cfgs = [RoundConfig(n_rounds=2, seed=seed),
            RoundConfig(n_rounds=2, seed=seed, ownership=True, n_owners=2)]
    hists = [run_rounds(task, pipe, cohort, cfg)[1] for cfg in cfgs]
    for hist in hists:
        for t in range(2):
            part = cohort.sample_round(seed, t)
            want = sum(
                codec.as_pipeline(pipe.replace(k=k_g)).payload_nbytes(1)
                * len(ids_g)
                for k_g, ids_g in cohort.budget_groups(part.survivors, pipe.k)
            )
            assert hist.bytes[t] == want
    # ownership changes the server's internal routing, never the wire ledger
    assert hists[0].bytes == hists[1].bytes
    assert hists[0].mse == hists[1].mse


# --------------------------------------------- (d) rho-tracker calibration

# small d with k close to it, so SparseProj's density correction F =
# 1 + (k-1)/d + 2(nnz-1)/(nnz d) is ~1.5: the pre-fix tracker (which applied
# the orthonormal-row d/k to sparse_proj) would read ~33% low here and fail
# the tolerance below by a wide margin.
RHO_D, RHO_K, RHO_N = 32, 16, 6

RHO_SPARSIFIERS = [
    ("rand_k", lambda: codec.RandK(k=RHO_K, d_block=RHO_D)),
    ("sparse_proj_s2", lambda: codec.SparseProj(k=RHO_K, d_block=RHO_D,
                                                s=2.0, transform="avg")),
    ("sparse_proj_s8", lambda: codec.SparseProj(k=RHO_K, d_block=RHO_D,
                                                s=8.0, transform="avg")),
    ("sparse_proj_s32", lambda: codec.SparseProj(k=RHO_K, d_block=RHO_D,
                                                 s=32.0, transform="avg")),
    ("identity", lambda: codec.Identity(d_block=RHO_D)),
]


@pytest.mark.parametrize("sp_name,sp_ctor", RHO_SPARSIFIERS,
                         ids=[s for s, _ in RHO_SPARSIFIERS])
def test_rho_tracker_calibration_known_cohorts(sp_name, sp_ctor):
    """``measure_rho`` on a known-rho cohort: within tolerance of the true
    rho AND never overclaiming, for every self-decodable sparsifier
    (sparse_proj at nnz = 16, 4 and 1 per row — the per-codec
    ``self_decode_norm_inflation`` de-inflation regression) x quantizer.

    The ground truth is r_exact over the ACTUAL cohort, not the nominal
    mixing rho, so the assertion is pure estimator calibration."""
    from repro.core import correlation
    from repro.fl import server as server_lib

    xs = _clients(0, n=RHO_N, c=C, d=RHO_D, rho=0.95)
    rho_true = float(np.clip(
        float(correlation.r_exact(xs)) / (RHO_N - 1), 0.0, 1.0))
    ids = list(range(RHO_N))
    for q_name, q_ctor in QUANTIZERS:
        pipe = _pipeline(sp_ctor, q_ctor)
        ests = []
        for t in range(32):
            key = jax.random.key(1000 + t)
            payloads, _ = pipe.encode_all(key, xs)
            ests.append(server_lib.measure_rho(pipe, key, payloads, ids))
        est = float(np.mean(ests))
        # calibration: observed |diff| <= 0.04 across the grid; the pre-fix
        # sparse_proj tracker read rho/F ~ rho - 0.28 here
        assert est >= rho_true - 0.08, (sp_name, q_name, est, rho_true)
        # the documented direction: residual ratio bias is toward 0, so the
        # tracker may underclaim but must never overclaim correlation
        assert est <= rho_true + 0.02, (sp_name, q_name, est, rho_true)


@pytest.mark.parametrize("sp", [
    codec.RandK(k=RHO_K, d_block=RHO_D),
    codec.SparseProj(k=RHO_K, d_block=RHO_D, s=2.0, transform="avg"),
    codec.SparseProj(k=RHO_K, d_block=RHO_D, s=8.0, transform="avg"),
    codec.SparseProj(k=RHO_K, d_block=RHO_D, s=32.0, transform="avg"),
], ids=["rand_k", "sparse_proj_s2", "sparse_proj_s8", "sparse_proj_s32"])
def test_self_decode_norm_inflation_matches_mc(sp):
    """The declared second-moment factor IS the measured one:
    E||self_decode(x)||^2 / ||x||^2 ≈ ``self_decode_norm_inflation``.

    For sparse_proj the declared factor carries the with-replacement
    correction F = 1 + (k-1)/d + 2(nnz-1)/(nnz d); the MC estimate must sit
    on the corrected value and clearly OFF the uncorrected d/k the tracker
    used before the fix."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((C, RHO_D)), jnp.float32)
    pipe = codec.as_pipeline(sp)

    @jax.jit
    def ratio(key):
        pl = pipe.encode_payload(key, 0, x)
        rec = pipe.self_decode(key, 0, pl)
        return jnp.sum(rec**2) / jnp.sum(x**2)

    keys = jax.random.split(jax.random.key(3), 600)
    mc = float(np.mean(np.asarray(jax.lax.map(ratio, keys))))
    declared = sp.self_decode_norm_inflation
    assert abs(mc - declared) / declared < 0.08, (mc, declared)
    uncorrected = sp.d_block / sp.k
    if declared > uncorrected:  # the sparse_proj cases
        assert abs(mc - declared) < abs(mc - uncorrected), (mc, declared)


# ------------------------------------------ (e) entropy-coded wire honesty


CODED_SPARSIFIERS = ["rand_k", "rand_k_spatial", "top_k", "wangni",
                     "induced", "identity", "sparse_proj"]


@pytest.mark.parametrize("q_name,q_ctor", QUANTIZERS,
                         ids=[q for q, _ in QUANTIZERS])
@pytest.mark.parametrize("sp_name", CODED_SPARSIFIERS)
def test_entropy_coded_ledger_honesty(sp_name, q_name, q_ctor):
    """The coded-size honesty contract, per sparsifier x quantizer (28
    cases): ``coded_nbytes`` equals the LENGTH of the stream ``encode_stream``
    actually emits, the stream round-trips bit-exactly under the declared
    schema, the stacked accounting is the per-client sum, and the store
    escape bounds every integer array at raw + 1 header byte."""
    from repro.core.codec.payload import arrays_of

    kw = {"transform": "avg"} if sp_name in ("rand_k_spatial",
                                             "sparse_proj") else {}
    if sp_name == "identity":
        sp = codec.Identity(d_block=D)
    else:
        sp = codec.SPARSIFIERS[sp_name](k=K, d_block=D, **kw)
    stages = [sp] + ([q_ctor()] if q_ctor is not None else [])
    stages.append(codec.EntropyCode())
    pipe = codec.Pipeline(stages)
    code = pipe.code_stage

    xs = _clients(7)
    key = jax.random.key(42)
    payloads, _ = pipe.encode_all(key, xs)
    per_client = [pipe.encode_payload(key, i, xs[i]) for i in range(N)]

    total = 0
    for pl in per_client:
        stream = code.encode_stream(pl)
        # the declared size IS the emitted stream's length
        assert code.coded_nbytes(pl) == len(stream)
        total += len(stream)
        # and the stream round-trips bit-exactly under the declared schema
        out = code.decode_stream(stream, pl.meta.schema)
        arrays = arrays_of(pl)
        assert set(out) == set(arrays)
        for name, a in arrays.items():
            a = np.asarray(a)
            assert out[name].dtype == a.dtype and out[name].shape == a.shape
            assert np.asarray(out[name]).tobytes() == a.tobytes(), name
        # escape bound: every integer array costs at most raw + 1 header byte
        n_int = sum(np.issubdtype(np.asarray(a).dtype, np.integer)
                    for a in arrays.values())
        assert len(stream) <= pl.nbytes + n_int

    # stacked accounting == per-client sum, through both entry points
    assert code.coded_nbytes_stacked(payloads) == total
    assert codec.coded_payload_nbytes(pipe, payloads) == total
    # without a code stage the same helper ledgers the raw actual bytes
    pipe_nc = codec.Pipeline(stages[:-1])
    pl_nc, _ = pipe_nc.encode_all(key, xs)
    assert codec.coded_payload_nbytes(pipe_nc, pl_nc) == pl_nc.nbytes


def test_entropy_store_escape_paths_round_trip():
    """Incompressible arrays take the 1-byte store escape instead of growing:
    full-range int8 noise (no Gaussian model wins), full-range int32 noise
    (no Rice parameter wins) — both bounded at raw + 1 and bit-exact."""
    from repro.core.codec.entropy import _decode_array, _encode_array

    rng = np.random.default_rng(0)
    cases = [
        rng.integers(-128, 128, size=512).astype(np.int8),
        rng.integers(-2**31, 2**31, size=256, dtype=np.int64).astype(np.int32),
    ]
    for a in cases:
        data = _encode_array(a)
        assert data[0] == 255  # the _STORE escape header
        assert len(data) == a.nbytes + 1
        out, end = _decode_array(data, 0, a.shape, a.dtype)
        assert end == len(data)
        np.testing.assert_array_equal(out, a)


def test_entropy_compresses_peaked_int8_and_small_indices():
    """The regimes the stage exists for: near-zero quantized values code far
    below 8 bits/symbol, small-range indices far below 32 — and both still
    round-trip bit-exactly (including extreme +-127 symbols)."""
    from repro.core.codec.entropy import _decode_array, _encode_array

    rng = np.random.default_rng(1)
    peaked = np.clip(np.round(rng.standard_normal(1024) * 4), -128,
                     127).astype(np.int8)
    idx = rng.integers(0, 64, size=(4, 64)).astype(np.int32)
    extremes = np.tile(np.array([-127, 127, 0], np.int8), 100)
    for a, bound in [(peaked, 0.7), (idx, 0.5), (extremes, 1.0)]:
        data = _encode_array(a)
        assert len(data) <= a.nbytes * bound + 1, (a.dtype, len(data), a.nbytes)
        out, end = _decode_array(data, 0, a.shape, a.dtype)
        assert end == len(data)
        np.testing.assert_array_equal(out, a)


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
    xs = _clients(9)
    payload = pipe.encode_payload(jax.random.key(0), 0, xs[0])
    assert codec.check_against_schema(payload) == []
    assert payload.nbytes == pipe.payload_nbytes(C)
    xhs = _mc_estimates(pipe, xs, None, trials=200, seed=900)
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
    xs = _clients(11)
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
        xhs = _mc_estimates(pipe, xs, None, trials=64, seed=77)
        mses[q_name] = float(np.mean(np.sum((xhs - xbar[None]) ** 2,
                                            axis=(1, 2))))
    assert mses["correlated"] < 0.85 * mses["int8"], mses
    # byte parity: the win is not bought with a bigger payload
    p_int8 = codec.Pipeline([codec.Identity(d_block=d), codec.Int8Quant()])
    p_corr = codec.Pipeline([codec.Identity(d_block=d),
                             codec.CorrelatedQuant()])
    assert p_int8.payload_nbytes(1) == p_corr.payload_nbytes(1)
