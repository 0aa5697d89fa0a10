"""Layer-pipelined application over a mesh axis (GPipe schedule).

``partition_blocks`` regroups a stacked-blocks param tree (n_blocks, ...)
into (n_stages, blocks_per_stage, ...); ``pipeline_apply`` runs the staged
blocks over microbatches with a shard_map: stage s holds its param shard,
activations hop stage-to-stage via ppermute, and the last stage's outputs
are broadcast back with a masked psum. Results are bit-identical to the
serial composition (the bubble only wastes compute, never reorders math).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def partition_blocks(tree, n_stages: int):
    """(n_blocks, ...) stacked params -> (n_stages, n_blocks//n_stages, ...)."""

    def one(leaf):
        nb = leaf.shape[0]
        if nb % n_stages:
            raise ValueError(f"n_blocks={nb} not divisible by n_stages={n_stages}")
        return leaf.reshape((n_stages, nb // n_stages) + leaf.shape[1:])

    return jax.tree.map(one, tree)


def pipeline_apply(stage_fn, staged, x, mesh, axis: str = "pipe"):
    """Apply staged blocks to microbatches x: (m, microbatch, ...).

    stage_fn(stage_params, h) applies one stage's blocks to activations h of
    shape x.shape[1:]. staged leaves: (n_stages, ...) sharded over ``axis``.
    Returns (m, microbatch, ...) — the serial composition of all stages.
    """
    n_stages = mesh.shape[axis]
    m = x.shape[0]
    n_steps = m + n_stages - 1  # pipeline depth: fill + drain bubble

    def shard_fn(staged_local, x_all):
        params = jax.tree.map(lambda leaf: jnp.squeeze(leaf, 0), staged_local)
        stage = jax.lax.axis_index(axis)

        def body(t, carry):
            state, outputs = carry
            # stage 0 ingests microbatch t; later stages consume the hop
            mb = jax.lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, m - 1), 0, keepdims=False
            )
            h = stage_fn(params, jnp.where(stage == 0, mb, state))
            # last stage retires microbatch t - (n_stages - 1)
            j = t - (n_stages - 1)
            valid = jnp.logical_and(j >= 0, j < m)
            jc = jnp.clip(j, 0, m - 1)
            prev = jax.lax.dynamic_index_in_dim(outputs, jc, 0, keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, h, prev), jc, 0
            )
            nxt = jax.lax.ppermute(
                h, axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            return nxt, outputs

        init = (jnp.zeros(x_all.shape[1:], x_all.dtype), jnp.zeros_like(x_all))
        _, outputs = jax.lax.fori_loop(0, n_steps, body, init)
        # only the last stage holds real outputs; broadcast via masked psum
        return jax.lax.psum(
            outputs * (stage == n_stages - 1).astype(outputs.dtype), axis
        )

    in_specs = (
        jax.tree.map(lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), staged),
        P(*([None] * x.ndim)),
    )
    out_specs = P(*([None] * x.ndim))
    return jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(staged, x)
