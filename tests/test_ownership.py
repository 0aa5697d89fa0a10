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

The plan, the owner-sliced decode core and the byte model are pinned here;
tree-level ownership (the 8-device subprocess and the train step included)
in `test_ownership_tree.py`, `fl.rounds` in `test_ownership_rounds.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ownership_cases import ALL_ESTIMATORS, D, K, client_tree
from repro.core import codec
from repro.dist import collectives
from repro.dist.sharding import ChunkOwnership, chunk_ownership


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
    tree = client_tree(np_rng)
    pipe = codec.as_pipeline(codec.RandK(k=K, d_block=D))
    _, info, _ = collectives.compressed_mean_tree(pipe, rng_key, tree)
    for k in ("n_shards", "intra_pod_bytes", "intra_pod_bytes_allgather",
              "intra_pod_bytes_ownership"):
        assert k in info
    mesh = jax.make_mesh((jax.device_count(),), ("pod",))
    _, info2, _ = collectives.compressed_mean_tree_shardmap(
        pipe, rng_key, tree, mesh, ownership=True)
    assert info2["n_shards"] == jax.device_count()
