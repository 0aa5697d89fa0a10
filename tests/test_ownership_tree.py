"""Sharded server decode (chunk ownership, docs/DESIGN.md §10), part 2:
tree-level ownership.

``compressed_mean_tree(ownership=)`` and
``compressed_mean_tree_shardmap(ownership=)`` are BIT-identical to the
replicated decode — with participants masks, error feedback and overlap
streaming — on one device, and on 4 real shards (``all_to_all`` payload
routing) in an 8-device subprocess, which also checks the intra-pod ledger
reduction; the DME train step's update is unchanged by ownership. The plan,
the owner-sliced decode core and the byte model are in
``test_ownership.py``; ``fl.rounds`` in ``test_ownership_rounds.py``.
"""
from __future__ import annotations

import sys
import textwrap

import jax
import numpy as np
import pytest

from ownership_cases import ALL_ESTIMATORS, D, K, assert_trees_equal, client_tree
from repro.core import codec
from repro.dist import collectives
from repro.dist.sharding import chunk_ownership
from support import run_child, src_env


# ------------------------------------------------------- tree-level ownership


@pytest.mark.parametrize("spec", ALL_ESTIMATORS,
                         ids=lambda s: codec.as_pipeline(s).describe())
def test_tree_ownership_parity_gspmd(spec, rng_key, np_rng):
    tree = client_tree(np_rng)
    pipe = codec.as_pipeline(spec)
    m0, i0, _ = collectives.compressed_mean_tree(pipe, rng_key, tree)
    m1, i1, _ = collectives.compressed_mean_tree(pipe, rng_key, tree,
                                                 ownership=3)
    assert_trees_equal(m0, m1)
    assert i1["n_shards"] == 3
    assert i1["intra_pod_bytes"] == i1["intra_pod_bytes_ownership"]
    assert i0["intra_pod_bytes"] == 0  # single logical shard, nothing crosses


def test_tree_ownership_with_participants_and_ef(rng_key, np_rng):
    tree = client_tree(np_rng)
    pipe = codec.Pipeline([codec.RandK(k=K, d_block=D), codec.ErrorFeedback()])
    part = [0, 2, 5]
    m0, _, e0 = collectives.compressed_mean_tree(
        pipe, rng_key, tree, participants=part)
    m1, _, e1 = collectives.compressed_mean_tree(
        pipe, rng_key, tree, participants=part, ownership=4)
    assert_trees_equal(m0, m1)
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


def test_tree_ownership_composes_with_overlap(rng_key, np_rng):
    tree = client_tree(np_rng)
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=K, d_block=D))
    m0, _, _ = collectives.compressed_mean_tree(pipe, rng_key, tree)
    for tile in (1, 2, 5):
        m1, _, _ = collectives.compressed_mean_tree(
            pipe, rng_key, tree, ownership=3, overlap=True, overlap_tile=tile)
        assert_trees_equal(m0, m1)


def test_tree_ownership_plan_mismatch_raises(rng_key, np_rng):
    tree = client_tree(np_rng)
    pipe = codec.as_pipeline(codec.RandK(k=K, d_block=D))
    with pytest.raises(ValueError, match="covers"):
        collectives.compressed_mean_tree(
            pipe, rng_key, tree, ownership=chunk_ownership(3, 2))


def test_shardmap_ownership_parity_one_device(rng_key, np_rng):
    """The shard_map route (all_to_all + all_gather of means) on however many
    local devices exist — the full multi-shard parity runs in the
    subprocess test below."""
    tree = client_tree(np_rng)
    mesh = jax.make_mesh((jax.device_count(),), ("pod",))
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=K, d_block=D))
    m0, _, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh)
    m1, i1, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh, ownership=True)
    assert_trees_equal(m0, m1)
    m2, _, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh, ownership=True, overlap=True,
        overlap_tile=2)
    assert_trees_equal(m0, m2)


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
@pytest.mark.limit(480)  # over the default: its child's own timeout is 420 s
def test_shardmap_ownership_multi_shard_in_subprocess():
    """4 real shards: all_to_all payload routing + all_gather of decoded
    means is bit-identical to the replicated all-gather decode for every
    estimator family, and the intra-pod ledger reduction holds."""
    out = run_child([sys.executable, "-c", _SUBPROC], timeout=420,
                    env=src_env())
    assert "SUBPROC_OK" in out.stdout, out.stderr[-2000:]
