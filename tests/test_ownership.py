"""Sharded server decode (chunk ownership, docs/DESIGN.md §10).

The tentpole claims pinned here:

1. **Plan** — `dist.sharding.ChunkOwnership` partitions the chunk grid into
   contiguous owner slices, divisibility-aware (exact tiling when divisible,
   logical padding otherwise), with every chunk owned by exactly one shard.
2. **Decode parity** — the owner-partitioned decode is BIT-identical to the
   monolithic decode for every registered estimator (position-keyed codecs
   re-derive randomness from the global chunk offset), through
   `sharded_decode`, `compressed_mean_tree(ownership=)`,
   `compressed_mean_tree_shardmap(ownership=)` (real `all_to_all` routing in
   an 8-device subprocess), and `fl.rounds` on all three backends —
   including participants masks, heterogeneous budgets, error feedback and
   overlap streaming.
3. **Ledger** — `info`/`History` gain the modelled `intra_pod_bytes`
   columns, and the ownership route strictly reduces intra-pod traffic at
   n_shards >= 2 whenever remote payload bytes exceed the decoded vector's
   d bytes.
4. **Rejection** — cross-chunk decode statistics (`rand_k_spatial` with
   `r_mode="est"`) are rejected with the offending stage named, never
   silently mis-decoded.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec
from repro.dist import collectives
from repro.dist.sharding import ChunkOwnership, chunk_ownership
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


# ------------------------------------------------------------------- the plan


def test_ownership_plan_divisible():
    plan = chunk_ownership(12, 4)
    assert plan.chunks_per_owner == 3
    assert plan.pad == 0 and plan.padded_chunks == 12
    assert plan.slices == ((0, 3), (3, 6), (6, 9), (9, 12))


def test_ownership_plan_ragged_pads_tail():
    plan = chunk_ownership(7, 3)
    assert plan.chunks_per_owner == 3
    assert plan.pad == 2 and plan.padded_chunks == 9
    assert plan.slices == ((0, 3), (3, 6), (6, 7))
    # every real chunk owned by exactly one shard, in slice order
    owners = [plan.owner_of(c) for c in range(7)]
    assert owners == [0, 0, 0, 1, 1, 1, 2]
    covered = [c for lo, hi in plan.slices for c in range(lo, hi)]
    assert covered == list(range(7))


def test_ownership_plan_more_shards_than_chunks():
    plan = chunk_ownership(2, 4)
    assert plan.chunks_per_owner == 1
    assert plan.slices == ((0, 1), (1, 2), (2, 2), (2, 2))  # empty tail owners


def test_ownership_plan_validates():
    with pytest.raises(ValueError, match="n_chunks"):
        ChunkOwnership(n_chunks=0, n_shards=2)
    with pytest.raises(ValueError, match="n_shards"):
        ChunkOwnership(n_chunks=4, n_shards=0)
    plan = chunk_ownership(4, 2)
    with pytest.raises(ValueError, match="out of range"):
        plan.owner_of(4)
    with pytest.raises(ValueError, match="out of range"):
        plan.slice_for(2)


# --------------------------------------------------- owner-sliced decode core


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

# rand_proj_spatial's online R-hat is a PER-CHUNK statistic (shardable), but
# its einsum contraction associates differently for different slice widths:
# numerically identical under ownership, not bitwise.
APPROX_ESTIMATORS = [
    codec.RandProjSpatial(k=K, d_block=D, transform="avg", r_mode="est"),
]


@pytest.mark.parametrize("spec", ALL_ESTIMATORS,
                         ids=lambda s: codec.as_pipeline(s).describe())
@pytest.mark.parametrize("n_shards", [1, 2, 3, 5, 7])
def test_sharded_decode_bitwise_parity(spec, n_shards, rng_key, np_rng):
    """Owner-partitioned decode == monolithic decode, bit for bit, for every
    registered sparsifier x quantizer — including ragged plans (7 % 3 != 0)
    and more shards than chunks territory."""
    n, c = 6, 7
    pipe = codec.as_pipeline(spec)
    xs = jnp.asarray(np_rng.standard_normal((n, c, D)), jnp.float32)
    payloads, _ = pipe.encode_all(rng_key, xs)
    full = pipe.decode_payload(rng_key, payloads, n)
    sharded = collectives.sharded_decode(
        pipe, rng_key, payloads, n, chunk_ownership(c, n_shards)
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(sharded))


@pytest.mark.parametrize("spec", APPROX_ESTIMATORS,
                         ids=lambda s: codec.as_pipeline(s).describe())
def test_sharded_decode_est_mode_allclose(spec, rng_key, np_rng):
    n, c = 6, 7
    pipe = codec.as_pipeline(spec)
    xs = jnp.asarray(np_rng.standard_normal((n, c, D)), jnp.float32)
    payloads, _ = pipe.encode_all(rng_key, xs)
    full = pipe.decode_payload(rng_key, payloads, n)
    for n_shards in (2, 3):
        sharded = collectives.sharded_decode(
            pipe, rng_key, payloads, n, chunk_ownership(c, n_shards)
        )
        np.testing.assert_allclose(np.asarray(full), np.asarray(sharded),
                                   rtol=1e-4, atol=1e-5)


def test_sharded_decode_with_participants(rng_key, np_rng):
    n, c = 8, 5
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=K, d_block=D))
    xs = jnp.asarray(np_rng.standard_normal((n, c, D)), jnp.float32)
    ids = jnp.asarray([1, 3, 6])
    payloads, _ = pipe.encode_all(rng_key, xs[jnp.asarray(ids)], client_ids=ids)
    full = pipe.decode_payload(rng_key, payloads, 3, client_ids=ids)
    sharded = collectives.sharded_decode(
        pipe, rng_key, payloads, 3, chunk_ownership(c, 2), client_ids=ids
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(sharded))


def test_sharded_decode_rejects_cross_chunk_statistics(rng_key, np_rng):
    """rand_k_spatial(r_mode='est') pools its R-hat across chunks: the
    rejection must name the offending stage class."""
    pipe = codec.as_pipeline(
        codec.RandKSpatial(k=K, d_block=D, transform="avg", r_mode="est"))
    assert not pipe.decode_shardable
    xs = jnp.asarray(np_rng.standard_normal((4, 4, D)), jnp.float32)
    payloads, _ = pipe.encode_all(rng_key, xs)
    with pytest.raises(ValueError, match="RandKSpatial") as ei:
        collectives.sharded_decode(pipe, rng_key, payloads, 4,
                                   chunk_ownership(4, 2))
    assert "decode-shardable" in str(ei.value)
    assert "R-hat" in str(ei.value)


def test_sharded_decode_rejects_sparse_proj_pooled_rhat(rng_key, np_rng):
    """sparse_proj(r_mode='est') pools its exact-adjoint R-hat across ALL
    chunks into one scalar (sparse rows overlap, so there is no per-chunk
    norm identity to shard on): the rejection must name SparseProj."""
    pipe = codec.as_pipeline(
        codec.SparseProj(k=K, d_block=D, transform="avg", r_mode="est"))
    assert not pipe.decode_shardable
    xs = jnp.asarray(np_rng.standard_normal((4, 4, D)), jnp.float32)
    payloads, _ = pipe.encode_all(rng_key, xs)
    with pytest.raises(ValueError, match="SparseProj") as ei:
        collectives.sharded_decode(pipe, rng_key, payloads, 4,
                                   chunk_ownership(4, 2))
    assert "decode-shardable" in str(ei.value)
    assert "R-hat" in str(ei.value)
    # ...and the fixed-transform modes shard bitwise (ALL_ESTIMATORS above):
    # the gate is about the pooled statistic, not the sparsifier per se.


# ------------------------------------------------------- tree-level ownership


@pytest.mark.parametrize("spec", ALL_ESTIMATORS,
                         ids=lambda s: codec.as_pipeline(s).describe())
def test_tree_ownership_parity_gspmd(spec, rng_key, np_rng):
    tree = _tree(np_rng)
    pipe = codec.as_pipeline(spec)
    m0, i0, _ = collectives.compressed_mean_tree(pipe, rng_key, tree)
    m1, i1, _ = collectives.compressed_mean_tree(pipe, rng_key, tree,
                                                 ownership=3)
    _assert_trees_equal(m0, m1)
    assert i1["n_shards"] == 3
    assert i1["intra_pod_bytes"] == i1["intra_pod_bytes_ownership"]
    assert i0["intra_pod_bytes"] == 0  # single logical shard, nothing crosses


def test_tree_ownership_with_participants_and_ef(rng_key, np_rng):
    tree = _tree(np_rng)
    pipe = codec.Pipeline([codec.RandK(k=K, d_block=D), codec.ErrorFeedback()])
    part = [0, 2, 5]
    m0, _, e0 = collectives.compressed_mean_tree(
        pipe, rng_key, tree, participants=part)
    m1, _, e1 = collectives.compressed_mean_tree(
        pipe, rng_key, tree, participants=part, ownership=4)
    _assert_trees_equal(m0, m1)
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


def test_tree_ownership_composes_with_overlap(rng_key, np_rng):
    tree = _tree(np_rng)
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=K, d_block=D))
    m0, _, _ = collectives.compressed_mean_tree(pipe, rng_key, tree)
    for tile in (1, 2, 5):
        m1, _, _ = collectives.compressed_mean_tree(
            pipe, rng_key, tree, ownership=3, overlap=True, overlap_tile=tile)
        _assert_trees_equal(m0, m1)


def test_tree_ownership_plan_mismatch_raises(rng_key, np_rng):
    tree = _tree(np_rng)
    pipe = codec.as_pipeline(codec.RandK(k=K, d_block=D))
    with pytest.raises(ValueError, match="covers"):
        collectives.compressed_mean_tree(
            pipe, rng_key, tree, ownership=chunk_ownership(3, 2))


def test_shardmap_ownership_parity_one_device(rng_key, np_rng):
    """The shard_map route (all_to_all + all_gather of means) on however many
    local devices exist — the full multi-shard parity runs in the
    subprocess test below."""
    tree = _tree(np_rng)
    mesh = jax.make_mesh((jax.device_count(),), ("pod",))
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=K, d_block=D))
    m0, _, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh)
    m1, i1, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh, ownership=True)
    _assert_trees_equal(m0, m1)
    m2, _, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh, ownership=True, overlap=True,
        overlap_tile=2)
    _assert_trees_equal(m0, m2)


# ------------------------------------------------------- intra-pod byte model


def test_intra_pod_traffic_reduction_regime():
    """At n_shards >= 2 the ownership route strictly reduces intra-pod bytes
    whenever remote clients' payload bytes exceed the decoded vector's
    d bytes ((n - n/s) * payload > C * d * 4), and the model says so."""
    pipe = codec.as_pipeline(codec.RandK(k=64, d_block=128))
    for n_shards in (2, 4, 8):
        t = collectives.intra_pod_traffic(pipe, n=16, n_chunks=8,
                                          n_shards=n_shards)
        assert t["intra_pod_bytes_ownership"] < t["intra_pod_bytes_allgather"]
    # inverted regime: tiny payloads, huge vector -> ownership loses, and the
    # model must say THAT too (the ledger is honest, not a sales pitch)
    tiny = codec.as_pipeline(codec.RandK(k=1, d_block=1024))
    t = collectives.intra_pod_traffic(tiny, n=2, n_chunks=8, n_shards=2)
    assert t["intra_pod_bytes_ownership"] > t["intra_pod_bytes_allgather"]


def test_intra_pod_traffic_single_shard_is_zero():
    pipe = codec.as_pipeline(codec.RandK(k=K, d_block=D))
    t = collectives.intra_pod_traffic(pipe, n=8, n_chunks=4, n_shards=1)
    assert t["intra_pod_bytes_allgather"] == 0
    assert t["intra_pod_bytes_ownership"] == 0
    assert t["intra_pod_bytes"] == 0


def test_intra_pod_reduction_helper():
    from repro.fl import server as server_lib

    pipe = codec.as_pipeline(codec.RandK(k=64, d_block=128))
    t = collectives.intra_pod_traffic(pipe, n=16, n_chunks=8, n_shards=4)
    r = server_lib.intra_pod_reduction(t)
    assert r is not None and r > 1.0
    assert server_lib.intra_pod_reduction(
        collectives.intra_pod_traffic(pipe, 16, 8, 1)) is None


def test_info_columns_present_on_both_entry_points(rng_key, np_rng):
    tree = _tree(np_rng)
    pipe = codec.as_pipeline(codec.RandK(k=K, d_block=D))
    _, info, _ = collectives.compressed_mean_tree(pipe, rng_key, tree)
    for k in ("n_shards", "intra_pod_bytes", "intra_pod_bytes_allgather",
              "intra_pod_bytes_ownership"):
        assert k in info
    mesh = jax.make_mesh((jax.device_count(),), ("pod",))
    _, info2, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh, ownership=True)
    assert info2["n_shards"] == jax.device_count()


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


# ------------------------------------------------------------------ train step


def test_train_step_ownership_parity():
    from repro import configs
    from repro.data import SyntheticLM
    from repro.models import init_params
    from repro.optim import AdamW
    from repro.train import make_train_step

    cfg = configs.reduce_for_smoke(configs.get_config("musicgen-medium"))
    opt = AdamW(lr=1e-2, warmup_steps=1)
    params = init_params(cfg, jax.random.key(0))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, batch=3,
                       n_clients=2)
    batch = data.batch_at(0)
    spec = codec.build("rand_k", k=64, d_block=512)
    s0 = jax.jit(make_train_step(cfg, opt, dme_spec=spec))
    s1 = jax.jit(make_train_step(cfg, opt, dme_spec=spec, dme_ownership=4))
    p0, _, m0 = s0(params, {"opt": opt.init(params)}, batch, 0)
    p1, _, m1 = s1(params, {"opt": opt.init(params)}, batch, 0)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert m1["intra_pod_reduction"] > 0


# ---------------------------------------------- real multi-shard routing (slow)


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import codec
    from repro.dist import collectives

    key = jax.random.key(0)
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.standard_normal((8, 40, 20)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((8, 33)), jnp.float32)}
    mesh = jax.make_mesh((4,), ("pod",))

    specs = [
        codec.RandProjSpatial(k=16, d_block=128),
        codec.RandK(k=16, d_block=128, shared_randomness=False),
        codec.Wangni(k=16, d_block=128),
        codec.Induced(k=16, d_block=128),
        codec.Identity(d_block=128),
        codec.Pipeline([codec.RandK(k=16, d_block=128), codec.Int8Quant()]),
    ]
    for spec in specs:
        pipe = codec.as_pipeline(spec)
        # warm any beta eigenvalue bank OUTSIDE the mesh trace: the
        # host-side bank simulation cannot run inside shard_map
        collectives.compressed_mean_tree(pipe, key, tree)
        m0, i0, _ = collectives.compressed_mean_tree_shardmap(
            pipe, key, tree, mesh)
        m1, i1, _ = collectives.compressed_mean_tree_shardmap(
            pipe, key, tree, mesh, ownership=True)
        for a, b in zip(jax.tree.leaves(m0), jax.tree.leaves(m1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert i1["n_shards"] == 4

    # participants + EF + overlap through the real all_to_all routing
    pipe_ef = codec.Pipeline([codec.RandK(k=16, d_block=128),
                              codec.ErrorFeedback()])
    m2, _, e2 = collectives.compressed_mean_tree_shardmap(
        pipe_ef, key, tree, mesh, participants=[0, 2, 5, 6, 7])
    m3, _, e3 = collectives.compressed_mean_tree_shardmap(
        pipe_ef, key, tree, mesh, participants=[0, 2, 5, 6, 7],
        ownership=True)
    for a, b in zip(jax.tree.leaves(m2), jax.tree.leaves(m3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(e3))
    m4, _, e4 = collectives.compressed_mean_tree_shardmap(
        pipe_ef, key, tree, mesh, ownership=True, overlap=True,
        overlap_tile=2)
    m5, _, e5 = collectives.compressed_mean_tree_shardmap(
        pipe_ef, key, tree, mesh)
    for a, b in zip(jax.tree.leaves(m4), jax.tree.leaves(m5)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(e4), np.asarray(e5))

    # the reduction regime, measured off the real route's info dict: n*k
    # payload bytes per chunk >> d bytes per chunk (warm the beta bank
    # OUTSIDE the mesh trace; host-side simulation cannot run inside it)
    big = {"w": jnp.asarray(rng.standard_normal((8, 64, 32)), jnp.float32)}
    pipe_big = codec.as_pipeline(
        codec.RandProjSpatial(k=64, d_block=128, beta_trials=8))
    collectives.compressed_mean_tree(pipe_big, key, big)
    mb0, ib0, _ = collectives.compressed_mean_tree_shardmap(
        pipe_big, key, big, mesh)
    mb1, ib1, _ = collectives.compressed_mean_tree_shardmap(
        pipe_big, key, big, mesh, ownership=True)
    for a, b in zip(jax.tree.leaves(mb0), jax.tree.leaves(mb1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    assert ib1["intra_pod_bytes_ownership"] < ib1["intra_pod_bytes_allgather"]
    print("SUBPROC_OK")
    """
)


@pytest.mark.slow
def test_shardmap_ownership_multi_shard_in_subprocess():
    """4 real shards: all_to_all payload routing + all_gather of decoded
    means is bit-identical to the replicated all-gather decode for every
    estimator family, and the intra-pod ledger reduction holds."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert "SUBPROC_OK" in out.stdout, out.stderr[-2000:]
