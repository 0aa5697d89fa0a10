"""Rand-k sparsification (Konecny & Richtarik 2018) — the paper's baseline.

Each client sends k of its d coordinates, chosen uniformly without
replacement; indices are re-derived from the shared round key, so only the k
values travel. Decode: x_hat = (1/n)(d/k) sum_i scatter(vals_i).
MSE (paper Eq. 1): (1/n^2)(d/k - 1) sum_i ||x_i||^2.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...kernels import ops as kops
from . import base


def _indices(spec, key, client_id, n_chunks: int,
             chunk_offset=0):
    """(C, k) int32 coordinate choices for one client.

    ``chunk_offset`` is the GLOBAL position of the first chunk: per-chunk
    randomness (shared_randomness=False) is keyed by global chunk id, so a
    chunk-slice decode (the sharded server decode, dist.sharding chunk
    ownership) re-derives exactly the indices of a full-array decode.
    """
    ckey = base.client_key(key, client_id)
    d, k = spec.d_block, spec.k
    if spec.shared_randomness:
        idx = jax.random.permutation(ckey, d)[:k]
        return jnp.broadcast_to(idx, (n_chunks, k))
    positions = chunk_offset + jnp.arange(n_chunks)
    keys = jax.vmap(base.chunk_key, in_axes=(None, 0))(ckey, positions)
    return jax.vmap(lambda kk: jax.random.permutation(kk, d)[:k])(keys)


def _budget_offsets(budgets):
    offs = [0]
    for b in budgets:
        offs.append(offs[-1] + b)
    return offs


def _budgeted_indices(spec, key, client_id, n_chunks: int):
    """Per-chunk index arrays ``[(k_0,), ..., (k_{C-1},)]`` for adaptive
    ``chunk_budgets`` — chunk c takes the first k_c entries of the (shared or
    chunk-keyed) permutation, so the draw at budget k_c is exactly the
    uniform-budget draw truncated/extended (same permutation prefix)."""
    budgets = spec.chunk_budgets
    if len(budgets) != n_chunks:
        raise ValueError(
            f"chunk_budgets has {len(budgets)} entries but the vector has "
            f"{n_chunks} chunks"
        )
    ckey = base.client_key(key, client_id)
    d = spec.d_block
    if spec.shared_randomness:
        perm = jax.random.permutation(ckey, d)
        return [perm[: budgets[ci]] for ci in range(n_chunks)]
    return [
        jax.random.permutation(base.chunk_key(ckey, ci), d)[: budgets[ci]]
        for ci in range(n_chunks)
    ]


def _budgeted_scatter(spec, key, vals_flat, ids):
    """(n, sum k_c) flat values -> (n, C, d) per-client unbiased estimates,
    each chunk scaled by its OWN d/k_c."""
    budgets = spec.chunk_budgets
    c = len(budgets)
    offs = _budget_offsets(budgets)
    d = spec.d_block

    def one(client_id, v):
        idxs = _budgeted_indices(spec, key, client_id, c)
        rows = [
            (d / budgets[ci])
            * jnp.zeros((d,), v.dtype).at[idxs[ci]].add(v[offs[ci]: offs[ci + 1]])
            for ci in range(c)
        ]
        return jnp.stack(rows)

    return jax.vmap(one)(ids, vals_flat)


def encode(spec, key, client_id, x_cd):
    c = x_cd.shape[0]
    if getattr(spec, "chunk_budgets", None) is not None:
        idxs = _budgeted_indices(spec, key, client_id, c)
        return {"vals": jnp.concatenate(
            [x_cd[ci, idxs[ci]] for ci in range(c)]
        )}
    idx = _indices(spec, key, client_id, c)
    vals = kops.select_coords(x_cd, idx, use_pallas=getattr(spec, "use_pallas", "auto"))
    return {"vals": vals}


def scatter_sum_and_counts(spec, key, vals, n, client_ids=None, chunk_offset=0):
    """Common Rand-k / Rand-k-Spatial decode plumbing.

    vals: (n, C, k) -> (sum (C, d), counts (C, d)) of scattered payloads.
    ``client_ids`` overrides the 0..n-1 id assignment (partial participation);
    ``chunk_offset`` is the global position of vals' first chunk (owner-sliced
    decode) — the scatter itself is per-chunk, so rows are independent.
    A client's indices are distinct within a chunk, so its scatter is a
    row-wise ``expand_coords``; the sum over clients adds them.
    """
    c = vals.shape[1]
    d = spec.d_block
    ids = jnp.arange(n) if client_ids is None else jnp.asarray(client_ids)
    use_pallas = getattr(spec, "use_pallas", "auto")

    def one(client_id, v):
        idx = _indices(spec, key, client_id, c, chunk_offset)
        s = kops.expand_coords(v, idx, d, use_pallas=use_pallas)
        m = kops.expand_coords(1.0, idx, d, use_pallas=use_pallas)
        return s, m

    ss, ms = jax.vmap(one)(ids, vals)
    return ss.sum(0), ms.sum(0)


def decode(spec, key, payloads, n, client_ids=None, chunk_offset=0):
    if getattr(spec, "chunk_budgets", None) is not None:
        if chunk_offset:
            raise ValueError(
                "adaptive chunk_budgets decode is not shardable "
                "(chunk_offset must be 0)"
            )
        ids = jnp.arange(n) if client_ids is None else jnp.asarray(client_ids)
        return _budgeted_scatter(spec, key, payloads["vals"], ids).sum(0) / n
    s, _ = scatter_sum_and_counts(spec, key, payloads["vals"], n, client_ids,
                                  chunk_offset)
    return (spec.d_block / (spec.k * n)) * s


def self_decode(spec, key, client_id, payload):
    """Unbiased per-client reconstruction (d/k) scatter(vals): what the server
    attributes to this client. Drives error feedback and the FL server's
    online correlation tracker (repro.fl.server)."""
    vals = payload["vals"]
    if getattr(spec, "chunk_budgets", None) is not None:
        ids = jnp.asarray(client_id)[None]
        return _budgeted_scatter(spec, key, vals[None], ids)[0]
    c = vals.shape[0]
    idx = _indices(spec, key, client_id, c)
    s = jnp.zeros((c, spec.d_block), vals.dtype)
    s = s.at[jnp.arange(c)[:, None], idx].add(vals)
    return (spec.d_block / spec.k) * s


CODEC = base.Codec(encode=encode, decode=decode, self_decode=self_decode)
base.register("rand_k", CODEC)
