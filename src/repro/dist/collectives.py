"""Compressed-mean collectives: the paper's DME as a cross-client gradient
exchange.

``compressed_mean_tree`` is the reference (GSPMD) path: ravel each client's
pytree, chunk to ``d_block`` (core.chunking), run the codec pipeline's
encode at every client (sparsifier + quantizer stages + error-feedback
residuals), decode the cross-client mean once at the "server", and unravel
back to the tree. Only the encoded payloads are notionally transmitted;
``info`` carries the exact byte accounting, read straight off the payload's
self-described ledger (``payload.meta`` — Konecny & Richtarik 2016-style
accuracy-vs-communication bookkeeping).

``compressed_mean_tree_shardmap`` is the explicit-collective path: clients
live on mesh ``client_axes``; each shard encodes its local clients' chunks,
payloads cross the wire via ``all_gather`` (payload-sized traffic — the
whole point of the estimator), and every shard decodes the identical mean.

Both entry points accept any codec-like object — a ``codec.Pipeline`` or a
bare sparsifier config (normalised via ``codec.as_pipeline``).

Error feedback (an ``ErrorFeedback`` stage in the pipeline): residual
buffers are (n_clients, C, d_block) chunk arrays threaded by the caller
(train_state["ef"] / ``ClientState.ef`` rows); the residual is rebuilt from
the pipeline's self-decode so its support is exactly the untransmitted
coordinates. On the shard_map path each residual row lives with its client's
shard (P(client_axes, None, None)) — no residual state ever crosses the
wire.

Partial participation (``participants``): a concrete (host-side) index array
naming the clients that actually report this round (repro.fl samples these).
Only participants encode/transmit; the decode re-derives THEIR randomness via
``client_ids`` and normalises by the actual participant count — never by the
sampled count (straggler renormalisation). Non-participants' EF residuals
carry over unchanged.

Overlapped collectives (``overlap=True``): both entry points can stream the
chunk axis through a double buffer — the encode of chunk tile c+1 is
enqueued (and, on an async backend, runs) while tile c's payload is in
flight / decoding, instead of encoding all C chunks, then decoding all C
chunks. On the shard_map path the per-tile ``all_gather`` IS the in-flight
payload, so encode genuinely overlaps cross-client traffic. The streamed
path is bit-identical to the synchronous one (asserted by
tests/test_async.py on all three fl backends); it therefore requires a
``chunk_streamable`` pipeline — per-chunk randomness independent of chunk
position (see ``codec.Pipeline.chunk_streamable``) — and raises otherwise
rather than silently changing the estimate.

Sharded server decode (``ownership=``, docs/DESIGN.md §10): a
``dist.sharding.ChunkOwnership`` plan assigns each mesh shard a contiguous
slice of the chunk grid. Instead of all-gathering EVERY per-client payload to
EVERY shard (server memory and intra-pod receive traffic O(n * k) per shard),
payloads for chunk c are routed only to c's owner (an ``all_to_all`` over
the client axes — reduce-scatter-style), the owner runs the codec decode for
its slice at its global chunk offset, and the global mean is assembled with
ONE ``all_gather`` of decoded means (d bytes per chunk, not n*k payload
bytes). Bit-identical to the unsharded decode for every ``decode_shardable``
pipeline (per-chunk decode reads only its own payload rows + its global
position — everything except ``rand_k_spatial(r_mode='est')``, whose online
R-hat pools statistics across chunks), with one float-level exception:
``rand_proj_spatial(r_mode='est')`` is decode-shardable (its R-hat is
per-chunk) but its einsum associates differently per slice width, so
est-mode parity is numerical rather than bitwise. ``info`` gains the
modelled ``intra_pod_bytes`` columns; at n_shards >= 2 the ownership route
strictly reduces intra-pod traffic whenever the remote clients' payload
bytes exceed the decoded vector's d bytes (asserted in tests + benchmarks).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..core import chunking
from ..core.codec import as_pipeline
from . import sharding as shard_lib


@dataclasses.dataclass(frozen=True)
class DmeShardings:
    """Sharding constraints for the GSPMD compressed-mean path: the leading
    (client) axis of chunk/payload arrays lives on ``client_axes``."""

    mesh: Any
    client_axes: tuple

    def constrain(self, x):
        spec = P(self.client_axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def constrain_tree(self, tree):
        return jax.tree.map(self.constrain, tree)


def dme_shardings(mesh, client_axes=("pod",)) -> DmeShardings | None:
    if mesh is None:
        return None
    axes = tuple(a for a in client_axes if a in mesh.axis_names)
    if not axes:
        return None
    return DmeShardings(mesh=mesh, client_axes=axes)


def _client_slice(tree, i):
    return jax.tree.map(lambda leaf: leaf[i], tree)


def _chunk_clients(tree, d_block: int):
    """Per-client ravel+chunk. tree leaves carry a leading client axis n.

    Returns (chunks (n, C, d_block), restore_fn for a single client, n).
    """
    n = jax.tree.leaves(tree)[0].shape[0]
    _, restore = chunking.tree_chunk(_client_slice(tree, 0), d_block)
    return _vmap_chunk(tree, d_block), restore, n


def _vmap_chunk(tree, d_block: int):
    """(n, ...) leaves -> (n, C, d_block) chunks, one client per row."""
    return jax.vmap(lambda t: chunking.tree_chunk(t, d_block)[0])(tree)


def _info(pipe, n: int, d_flat: int, n_chunks: int, n_total: int | None = None,
          n_shards: int = 1, plan=None) -> dict:
    # declared ledger from the payload schema; the ledger-honesty tests pin
    # it to the actual array bytes, so declared == transmitted.
    per_client = pipe.payload_nbytes(n_chunks)
    return {
        "n_clients": n,
        "n_total": n if n_total is None else n_total,  # rows in the input tree
        "n_chunks": n_chunks,
        "d_flat": d_flat,
        "d_block": pipe.d_block,
        "full_bytes": d_flat * 4,  # uncompressed float32 exchange baseline
        "payload_bytes_per_client": per_client,
        "bytes_sent": per_client * n,
        **intra_pod_traffic(pipe, n, n_chunks, n_shards, plan=plan),
    }


def intra_pod_traffic(pipe, n: int, n_chunks: int, n_shards: int,
                      plan=None) -> dict:
    """Modelled server-side (intra-pod) RECEIVE bytes of one decode, summed
    over all shards — the quantity the sharded decode exists to cut:

    - ``intra_pod_bytes_allgather``: the replicated decode all-gathers every
      remote client's full payload to every shard:
      ``n_shards * n_remote * payload_nbytes(n_chunks)``.
    - ``intra_pod_bytes_ownership``: the ownership route delivers each shard
      only its owned chunk slice (``all_to_all``), then assembles decoded
      means (d_block float32 bytes per chunk) with one ``all_gather``:
      ``n_shards * n_remote * payload_nbytes(chunks_per_owner)
      + n_shards * (n_shards - 1) * chunks_per_owner * d_block * 4``.
    - ``intra_pod_bytes``: the column for the route actually taken
      (``ownership`` when a plan is in force, else ``allgather``).

    ``n_remote = n - n/n_shards`` is the clients whose payloads must cross a
    shard boundary to reach one given shard. At ``n_shards == 1`` everything
    is shard-local and all columns are 0. The ownership column counts the
    PADDED slice width (what ``all_to_all`` actually moves).
    """
    if n_shards <= 1:
        return {
            "n_shards": max(1, n_shards),
            "intra_pod_bytes_allgather": 0,
            "intra_pod_bytes_ownership": 0,
            "intra_pod_bytes": 0,
        }
    n_remote = n - n / n_shards
    allgather = n_shards * n_remote * pipe.payload_nbytes(n_chunks)
    eff = plan if plan is not None else shard_lib.chunk_ownership(n_chunks, n_shards)
    cpo = eff.chunks_per_owner
    ownership = (
        n_shards * n_remote * pipe.payload_nbytes(cpo)
        + n_shards * (n_shards - 1) * cpo * pipe.d_block * 4
    )
    return {
        "n_shards": n_shards,
        "intra_pod_bytes_allgather": int(round(allgather)),
        "intra_pod_bytes_ownership": int(round(ownership)),
        "intra_pod_bytes": int(round(ownership if plan is not None else allgather)),
    }


def intra_pod_reduction(info: dict) -> float | None:
    """allgather/ownership server-side traffic ratio from an ``info`` dict
    (``compressed_mean_tree*`` or ``intra_pod_traffic``). > 1 means the
    sharded decode receives fewer bytes than the replicated all-gather
    decode. None when the decode ran on a single shard (nothing crosses a
    shard boundary either way)."""
    own = info.get("intra_pod_bytes_ownership", 0)
    ag = info.get("intra_pod_bytes_allgather", 0)
    if not own or not ag:
        return None
    return ag / own


def ownership_plan(ownership, n_chunks: int, n_shards: int):
    """Normalise the ``ownership=`` argument: None/False -> no plan;
    True -> plan over ``n_shards``; int -> plan over that many shards;
    a ``ChunkOwnership`` -> validated pass-through."""
    if ownership is None or ownership is False:
        return None
    if isinstance(ownership, shard_lib.ChunkOwnership):
        if ownership.n_chunks != n_chunks:
            raise ValueError(
                f"ownership plan covers {ownership.n_chunks} chunks but the "
                f"payload grid has {n_chunks}"
            )
        return ownership
    if ownership is True:
        return shard_lib.chunk_ownership(n_chunks, max(1, n_shards))
    return shard_lib.chunk_ownership(n_chunks, int(ownership))


def _participant_ids(participants, n_total: int) -> np.ndarray:
    """Normalise a participation mask/index list to a concrete id array."""
    p = np.asarray(participants)
    if p.dtype == bool:
        p = np.flatnonzero(p)
    if p.size == 0:
        raise ValueError("participation mask selects zero clients")
    if p.max() >= n_total or p.min() < 0:
        raise ValueError(f"participant id out of range [0, {n_total})")
    return p.astype(np.int32)


def check_streamable(pipe) -> None:
    """Raise unless ``pipe`` may stream the chunk axis (``overlap=True``),
    naming the offending stage so the caller knows what to change."""
    offender = pipe.non_streamable_stage
    if offender is not None:
        stage, reason = offender
        raise ValueError(
            "overlap=True needs a chunk-streamable pipeline (per-chunk "
            "randomness independent of chunk position), but stage "
            f"{type(stage).__name__} of {pipe.describe()!r} {reason}. "
            "Run it with overlap=False instead."
        )


def check_shardable(pipe) -> None:
    """Raise unless ``pipe`` may decode owner-sliced (``ownership=``),
    naming the offending stage. Weaker than ``check_streamable``: clients
    always encode full vectors, only the DECODE must be chunk-local."""
    offender = pipe.non_shardable_stage
    if offender is not None:
        stage, reason = offender
        raise ValueError(
            "ownership= needs a decode-shardable pipeline (per-chunk decode "
            "reading only its own payload rows), but stage "
            f"{type(stage).__name__} of {pipe.describe()!r} {reason}. "
            "Run it without ownership instead."
        )


def stream_tiles(n_chunks: int, tile: int = 1, ownership=None) -> list:
    """Chunk-axis tiling for the double-buffered stream: [(lo, hi), ...].

    With an ``ownership`` plan the tiling becomes OWNER-LOCAL: tiles never
    span an owner boundary, so each tile's decode runs wholly on one owner
    and ``overlap=`` composes with the sharded decode. Owner slices are
    contiguous and ascending, so the tiles still cover [0, n_chunks) in
    natural order.
    """
    if tile < 1:
        raise ValueError(f"overlap_tile must be >= 1, got {tile}")
    if ownership is None:
        return [(lo, min(lo + tile, n_chunks)) for lo in range(0, n_chunks, tile)]
    tiles = []
    for s in range(ownership.n_shards):
        lo, hi = ownership.slice_for(s)
        tiles.extend((l0, min(l0 + tile, hi)) for l0 in range(lo, hi, tile))
    return tiles


def sharded_decode(pipe, key, payloads, n: int, plan, *, client_ids=None):
    """Owner-partitioned server decode of a stacked payload (leading client
    axis): decode each owner's chunk slice at its global offset and
    concatenate. This is the decode the shard_map ownership path runs
    per-owner; here all owners run in ONE batched (vmapped) decode call — the
    chunk axis is padded to ``plan.padded_chunks`` and reshaped owner-major,
    so every owner decodes an equal-width slice and no per-owner Python loop
    (or per-owner compilation) remains. Padded tail chunks decode from
    all-zero payloads (every registered codec maps them to finite values;
    the fused rand_proj_spatial CG converges on them at iteration 0) and are
    dropped before returning. This makes the partition testable anywhere and
    serves the local/gspmd backends.

    Bit-identical to ``pipe.decode_payload(key, payloads, n)`` for every
    ``decode_shardable`` pipeline: per-chunk decode reads only its own
    payload rows, and position-keyed randomness is re-derived from the
    GLOBAL chunk id via ``chunk_offset``. Sole float-level exception:
    ``rand_proj_spatial(r_mode='est', decode_method='gram')`` — the gram
    R-hat einsum associates differently per slice width, so parity there is
    numerical (allclose), not bitwise (tests/test_ownership.py pins both
    contracts; the fused decode's R-hat is per-chunk elementwise and exact).
    """
    check_shardable(pipe)
    cpo = plan.chunks_per_owner
    pad = plan.padded_chunks - plan.n_chunks
    padded = payloads
    if pad:
        padded = jax.tree.map(
            lambda leaf: jnp.pad(leaf, [(0, 0), (0, pad)] + [(0, 0)] * (leaf.ndim - 2)),
            payloads,
        )
    tiles = jax.tree.map(
        lambda leaf: jnp.moveaxis(
            leaf.reshape(leaf.shape[0], plan.n_shards, cpo, *leaf.shape[2:]), 1, 0
        ),
        padded,
    )
    offsets = jnp.arange(plan.n_shards) * cpo

    def owner_decode(tile, lo):
        return pipe.decode_payload(key, tile, n, client_ids=client_ids,
                                   chunk_offset=lo)

    outs = jax.vmap(owner_decode)(tiles, offsets)  # (n_shards, cpo, d_block)
    return outs.reshape(plan.padded_chunks, *outs.shape[2:])[: plan.n_chunks]


def _double_buffer(tiles, produce, consume) -> list:
    """The overlap idiom, in one place: ``produce(tile c+1)`` is enqueued
    BEFORE ``consume`` of tile c — so on an async backend the next tile's
    encode runs while the previous tile's payload is in flight / decoding.
    Returns ``[consume(tile, produce(tile)) for tile in tiles]`` evaluated
    in that staggered order."""
    outs: list = []
    in_flight = None
    for t in tiles:
        entry = produce(t)
        if in_flight is not None:
            outs.append(consume(*in_flight))
        in_flight = (t, entry)
    outs.append(consume(*in_flight))
    return outs


def streamed_mean(pipe, key, x, n, *, client_ids=None, side_info=None,
                  tile: int = 1, need_self: bool = False, constrain=None,
                  ownership=None):
    """Double-buffered chunk streaming: encode tile c+1 while tile c decodes.

    ``x``: (n, C, d_block) chunk array (EF residual already added by the
    caller); ``side_info``: (C, d_block) broadcast side information — the
    tile's slice is subtracted before encode and added back after decode,
    exactly as ``Pipeline.encode``/``decode`` would. ``constrain`` optionally
    applies a sharding constraint to each tile's payload leaves.

    ``ownership`` (a ``ChunkOwnership`` plan) makes the tile iteration
    OWNER-LOCAL: tiles never span an owner's slice boundary and each tile is
    decoded at its global chunk offset, so the stream is exactly the decode
    an owner shard would run — ``overlap=`` composes with the sharded decode
    without changing a bit (streamable pipelines are position-free).

    Returns (mean (C, d_block), self_dec (n, C, d_block) | None). For
    chunk-streamable pipelines (validated here) the result is BIT-identical
    to the synchronous encode_all -> decode_payload: tiles only reorder
    work, never the numbers. The ordering is what buys the overlap — each
    tile's encode is enqueued before the previous tile's decode, so an async
    backend runs them concurrently while the payload is notionally on the
    wire.
    """
    check_streamable(pipe)
    if ownership is not None:
        check_shardable(pipe)
    n_chunks = x.shape[1]
    ids = jnp.arange(n) if client_ids is None else jnp.asarray(client_ids)

    def produce(t):
        lo, hi = t
        x_tile = x[:, lo:hi]
        if side_info is not None:
            x_tile = x_tile - side_info[None, lo:hi]
        payloads, _ = pipe.encode_all(key, x_tile, client_ids=ids)
        return payloads if constrain is None else constrain(payloads)

    def consume(t, payloads):
        lo, hi = t
        dec = pipe.decode_payload(key, payloads, n, client_ids=ids,
                                  chunk_offset=lo)
        if side_info is not None:
            dec = dec + side_info[lo:hi]
        self_dec = None
        if need_self:
            self_dec = jax.vmap(
                lambda i, p: pipe.self_decode(key, i, p)
            )(ids, payloads)
        return dec, self_dec

    drained = _double_buffer(stream_tiles(n_chunks, tile, ownership),
                             produce, consume)
    mean = jnp.concatenate([d for d, _ in drained], axis=0)
    self_dec = (
        jnp.concatenate([s for _, s in drained], axis=1) if need_self else None
    )
    return mean, self_dec


def compressed_mean_tree(spec, key, tree, shardings=None, ef_chunks=None,
                         participants=None, overlap=False, overlap_tile=1,
                         ownership=None):
    """Cross-client compressed mean of a pytree.

    tree leaves: (n_clients, ...). Returns (mean_tree, info, ef_next) where
    mean_tree drops the client axis, info is static byte/payload accounting,
    and ef_next is the updated (n, C, d_block) residual (None unless the
    pipeline has an ErrorFeedback stage).

    ``participants``: concrete index array / bool mask of reporting clients.
    Only they encode; decode uses their actual client ids and n = how many
    actually reported. ef_next keeps the FULL (n_clients, ...) shape — rows of
    non-participants carry over unchanged.

    ``ownership``: True / shard count / ``ChunkOwnership`` plan — run the
    server decode owner-partitioned (``sharded_decode``; on this GSPMD path
    the owners are logical, so the partition changes no numbers and no
    traffic, but the same slices and chunk offsets as the shard_map route
    are exercised and ``info`` reports the modelled ``intra_pod_bytes``
    columns at the plan's shard count).
    """
    pipe = as_pipeline(spec)
    chunks, restore, n_total = _chunk_clients(tree, pipe.d_block)
    n_chunks = chunks.shape[1]
    mesh_shards = 1
    if shardings is not None:
        for a in shardings.client_axes:
            mesh_shards *= shardings.mesh.shape[a]
    plan = ownership_plan(ownership, n_chunks, mesh_shards)
    if plan is not None:
        check_shardable(pipe)
    if participants is None:
        ids = None
        part_chunks, n = chunks, n_total
    else:
        ids = _participant_ids(participants, n_total)
        part_chunks, n = chunks[ids], len(ids)
    if shardings is not None:
        part_chunks = shardings.constrain(part_chunks)
    x = part_chunks
    if pipe.has_ef:
        if ef_chunks is None:
            ef_chunks = jnp.zeros_like(chunks)
        x = part_chunks + (ef_chunks if ids is None else ef_chunks[ids])

    if overlap:
        mean_chunks, self_dec = streamed_mean(
            pipe, key, x, n, client_ids=ids, tile=overlap_tile,
            need_self=pipe.has_ef,
            constrain=None if shardings is None else shardings.constrain_tree,
            ownership=plan,
        )
    else:
        # walltime spans on the round-phase tracks (timing/attribution only —
        # byte annotations stay with the fl driver, which owns the ledger)
        with obs.span("dist", "client_encode", track="client_encode",
                      clients=n):
            payloads, _ = pipe.encode_all(key, x, client_ids=ids)
        if shardings is not None:
            payloads = shardings.constrain_tree(payloads)
        with obs.span("dist", "owner_decode", track="owner_decode",
                      clients=n, sharded=plan is not None):
            if plan is not None:
                mean_chunks = sharded_decode(pipe, key, payloads, n, plan,
                                             client_ids=ids)
            else:
                mean_chunks = pipe.decode_payload(key, payloads, n,
                                                  client_ids=ids)
        self_dec = None
        if pipe.has_ef:
            id_arr = jnp.arange(n) if ids is None else jnp.asarray(ids)
            self_dec = jax.vmap(
                lambda i, p: pipe.self_decode(key, i, p)
            )(id_arr, payloads)
    mean_tree = restore(mean_chunks)

    ef_next = None
    if pipe.has_ef:
        resid = x - self_dec
        ef_next = resid if ids is None else ef_chunks.at[jnp.asarray(ids)].set(resid)

    d_flat = sum(
        int(np.prod(leaf.shape[1:], dtype=np.int64)) for leaf in jax.tree.leaves(tree)
    )
    n_shards = plan.n_shards if plan is not None else mesh_shards
    return mean_tree, _info(pipe, n, d_flat, n_chunks, n_total=n_total,
                            n_shards=n_shards, plan=plan), ef_next


def compressed_mean_tree_shardmap(spec, key, grads, mesh, param_pspecs=None,
                                  client_axes=("pod",), ef_chunks=None,
                                  participants=None, overlap=False,
                                  overlap_tile=1, ownership=None):
    """Explicit-collective compressed mean via shard_map.

    grads leaves: (n_clients, ...) with the client axis sharded over
    ``client_axes``. Each shard chunks + encodes its local clients, payloads
    are all-gathered across the client axes (the only payload-sized cross-
    client traffic), and every shard runs the identical server decode.
    Requires n_clients divisible by the client-axes extent; falls back to the
    GSPMD path otherwise, with a ``RuntimeWarning`` that says so.

    Error feedback (ErrorFeedback stage): ``ef_chunks`` (n, C, d_block) is
    sharded over the client axis, so each residual row lives with its
    client's shard and never crosses the wire; the updated residual returns
    with the same sharding. Parity with the GSPMD path is asserted by
    tests/test_error_feedback.py.

    ``participants``: concrete ids/mask of reporting clients. Every shard
    still encodes all its local clients (static shapes), but only the
    participants' payloads enter the decode (static gather on the replicated
    payload stack, with their actual client ids) and only their residual rows
    update.

    ``ownership`` (True / ``ChunkOwnership``; docs/DESIGN.md §10): the
    sharded server decode. Instead of all-gathering every payload to every
    shard, an ``all_to_all`` over the client axes routes each chunk's
    payloads ONLY to its owner shard (reduce-scatter-style: the payload
    chunk axis is split, the client axis concatenated), the owner decodes
    its slice at its global chunk offset, and the decoded means — d_block
    float32 bytes per chunk, not n*k payload bytes — are assembled with one
    ``all_gather``. Bit-identical to the unsharded decode (asserted in
    tests/test_ownership.py, incl. participants, heterogeneous budgets and
    EF; ``rand_proj_spatial(r_mode='est')`` is the one float-level-only
    case — see ``sharded_decode``); EF residuals still never cross the wire
    (self-decode runs on the client's own shard from its pre-routing
    payloads).
    """
    pipe = as_pipeline(spec)
    client_axes = tuple(a for a in client_axes if a in mesh.axis_names)
    n = jax.tree.leaves(grads)[0].shape[0]
    n_shards = 1
    for a in client_axes:
        n_shards *= mesh.shape[a]
    if not client_axes or n % n_shards != 0:
        warnings.warn(
            f"compressed_mean_tree_shardmap: {n} clients do not divide over "
            f"mesh client axes {client_axes} ({n_shards} shards); running the "
            "GSPMD compressed_mean_tree instead", RuntimeWarning, stacklevel=2)
        return compressed_mean_tree(
            pipe, key, grads, dme_shardings(mesh, client_axes),
            ef_chunks=ef_chunks, participants=participants,
            overlap=overlap, overlap_tile=overlap_tile, ownership=ownership,
        )
    if overlap:
        check_streamable(pipe)
    n_local = n // n_shards

    part_ids = None if participants is None else _participant_ids(participants, n)
    n_eff = n if part_ids is None else len(part_ids)
    part_mask = np.ones(n, bool)
    if part_ids is not None:
        part_mask = np.zeros(n, bool)
        part_mask[part_ids] = True

    template = _client_slice(grads, 0)
    _, restore = chunking.tree_chunk(template, pipe.d_block)
    d_flat = sum(
        int(np.prod(leaf.shape[1:], dtype=np.int64)) for leaf in jax.tree.leaves(grads)
    )
    n_chunks = chunking.num_chunks(d_flat, pipe.d_block)
    plan = ownership_plan(ownership, n_chunks, n_shards)
    if plan is not None:
        if plan.n_shards != n_shards:
            raise ValueError(
                f"ownership plan has {plan.n_shards} owners but the mesh "
                f"client axes {client_axes} hold {n_shards} shards"
            )
        check_shardable(pipe)
    if pipe.has_ef and ef_chunks is None:
        ef_chunks = jnp.zeros((n, n_chunks, pipe.d_block), jnp.float32)
    use_ef = pipe.has_ef

    def local_fn(key, g_local, ef_local):
        shard_idx = jnp.zeros((), jnp.int32)
        for a in client_axes:
            shard_idx = shard_idx * mesh.shape[a] + jax.lax.axis_index(a)
        ids = shard_idx * n_local + jnp.arange(n_local)
        chunks = _vmap_chunk(g_local, pipe.d_block)
        x = chunks + ef_local if use_ef else chunks

        def encode_local(x_cols):
            return jax.vmap(
                lambda i, c: pipe.encode_payload(key, i, c)
            )(ids, x_cols)

        def encode_and_gather(x_tile):
            payloads = encode_local(x_tile)
            gathered = jax.tree.map(
                lambda leaf: jax.lax.all_gather(
                    leaf, client_axes, axis=0, tiled=True
                ),
                payloads,
            )
            return payloads, gathered

        def route_to_owners(payloads):
            """The reduce-scatter-style payload routing: split the chunk axis
            across the client axes, concatenate the client axis — this shard
            receives ONLY the slice it owns, from every client."""
            return jax.tree.map(
                lambda leaf: jax.lax.all_to_all(
                    leaf, client_axes, split_axis=1, concat_axis=0, tiled=True
                ),
                payloads,
            )

        def decode_owned(routed, owner_lo):
            """This shard's server decode of its owned slice, at its global
            chunk offset (position-keyed codecs re-derive the full decode's
            randomness from it)."""
            if part_ids is None:
                return pipe.decode_payload(key, routed, n, chunk_offset=owner_lo)
            selected = jax.tree.map(lambda leaf: leaf[part_ids], routed)
            return pipe.decode_payload(key, selected, n_eff,
                                       client_ids=part_ids,
                                       chunk_offset=owner_lo)

        def decode_gathered(gathered):
            if part_ids is None:
                return pipe.decode_payload(key, gathered, n)
            selected = jax.tree.map(lambda leaf: leaf[part_ids], gathered)
            return pipe.decode_payload(key, selected, n_eff, client_ids=part_ids)

        def local_self_dec(payloads):
            return jax.vmap(
                lambda i, p: pipe.self_decode(key, i, p)
            )(ids, payloads)

        def pad_chunk_axis(tree_like, pad):
            if pad == 0:
                return tree_like
            return jax.tree.map(
                lambda leaf: jnp.pad(
                    leaf, [(0, 0), (0, pad)] + [(0, 0)] * (leaf.ndim - 2)
                ),
                tree_like,
            )

        def assemble(mean_own):
            """(chunks_per_owner, d_block) decoded slice -> replicated
            (n_chunks, d_block): ONE all_gather of d-sized means — the only
            post-routing cross-shard traffic."""
            full = jax.lax.all_gather(mean_own, client_axes, axis=0, tiled=True)
            return full[:n_chunks]

        if plan is not None:
            cpo = plan.chunks_per_owner
            owner_lo = shard_idx * cpo
            if not overlap:
                payloads = encode_local(x)
                routed = route_to_owners(pad_chunk_axis(payloads, plan.pad))
                mean_chunks = assemble(decode_owned(routed, owner_lo))
                if not use_ef:
                    return restore(mean_chunks), ef_local
                self_dec = local_self_dec(payloads)
            else:
                # owner-local tile streaming: tile t covers positions
                # [lo, hi) of EVERY owner's slice at once, so the per-tile
                # all_to_all is the in-flight payload and each owner decodes
                # its sub-tile while the next tile encodes.
                x_pad = jnp.pad(x, ((0, 0), (0, plan.pad), (0, 0)))
                tile_cols = [
                    np.concatenate(
                        [s * cpo + np.arange(lo, hi) for s in range(n_shards)]
                    )
                    for lo, hi in stream_tiles(cpo, overlap_tile)
                ]

                def produce(cols):
                    payloads = encode_local(x_pad[:, cols])
                    return payloads, route_to_owners(payloads)

                def consume(cols, e):
                    dec = decode_owned(e[1], owner_lo + cols[0])
                    return dec, local_self_dec(e[0]) if use_ef else None

                drained = _double_buffer(tile_cols, produce, consume)
                mean_chunks = assemble(
                    jnp.concatenate([m for m, _ in drained], axis=0)
                )
                if not use_ef:
                    return restore(mean_chunks), ef_local
                # tiles saw owner-major column order: invert the (static)
                # permutation to put the self-decodes back in natural order
                col_order = np.concatenate(tile_cols)
                self_cat = jnp.concatenate([s for _, s in drained], axis=1)
                self_dec = self_cat[:, np.argsort(col_order)][:, :n_chunks]
        elif not overlap:
            payloads, gathered = encode_and_gather(x)
            mean_chunks = decode_gathered(gathered)
            if not use_ef:
                return restore(mean_chunks), ef_local
            self_dec = local_self_dec(payloads)
        else:
            # the per-tile all_gather IS the in-flight payload here
            drained = _double_buffer(
                stream_tiles(n_chunks, overlap_tile),
                lambda t: encode_and_gather(x[:, t[0]:t[1]]),
                lambda t, e: (decode_gathered(e[1]),
                              local_self_dec(e[0]) if use_ef else None),
            )
            mean_chunks = jnp.concatenate([m for m, _ in drained], axis=0)
            if not use_ef:
                return restore(mean_chunks), ef_local
            self_dec = jnp.concatenate([s for _, s in drained], axis=1)

        # residual update stays on the client's shard; non-participants keep
        # their residual (they did not transmit this round)
        resid = x - self_dec
        local_part = jnp.take(jnp.asarray(part_mask), ids)
        ef_next = jnp.where(local_part[:, None, None], resid, ef_local)
        return restore(mean_chunks), ef_next

    if ef_chunks is None:  # dummy carried buffer keeps one code path
        ef_chunks = jnp.zeros((n, 1, 1), jnp.float32)
    client_spec = P(client_axes, None, None)
    in_specs = (
        P(),
        jax.tree.map(lambda leaf: P(client_axes, *([None] * (leaf.ndim - 1))), grads),
        client_spec,
    )
    mean_specs = jax.tree.map(lambda leaf: P(*([None] * leaf.ndim)), template)
    # ``local_fn`` is traced by shard_map, so per-phase spans cannot live
    # inside it; the whole exchange gets one payload_route span (encode +
    # all_gather/all_to_all + decode run fused in the traced program)
    with obs.span("dist", "payload_route", track="payload_route",
                  backend="shard_map", shards=n_shards):
        mean_tree, ef_next = jax.shard_map(
            local_fn, mesh=mesh, in_specs=in_specs,
            out_specs=(mean_specs, client_spec), check_vma=False,
        )(key, grads, ef_chunks)
    if not use_ef:
        ef_next = None

    return mean_tree, _info(pipe, n_eff, d_flat, n_chunks, n_total=n,
                            n_shards=n_shards, plan=plan), ef_next


def psum_scatter_mean(tiles, counts, mesh, axis: str = "pod"):
    """Count-weighted mean of pre-placed per-pod tiles via ``psum_scatter``.

    The cross-pod combine of the hierarchical decode (docs/DESIGN.md §11) as
    a real device collective: ``tiles`` is (P, C, d_block) with row p — pod
    p's decoded d-sized estimate — pre-placed on shard p of mesh ``axis``;
    ``counts`` is (P,) contributing client counts (0 marks an absent pod, a
    row whose values are then irrelevant). Each shard contributes
    ``counts[p] * tiles[p]``, a ``psum_scatter`` reduces the weighted sum
    while leaving each shard exactly 1/P of the chunk axis (DCN traffic
    (P-1)/P of the naive all-reduce), and one ``all_gather`` of the
    normalised slices replicates the mean:

        sum_p counts[p] * tiles[p] / sum_p counts[p]    (C, d_block)

    ``counts`` must sum to > 0. The chunk axis is padded to a multiple of P
    internally. On a 1-shard mesh this degenerates to the weighted mean with
    no collective traffic. The KV-store exchange in ``runtime.comms`` is the
    CPU-backend equivalent of this combine (multiprocess XLA collectives are
    unavailable there); on TPU/GPU meshes this is the fast path.
    """
    n_shards = mesh.shape[axis]
    tiles = jnp.asarray(tiles)
    counts = jnp.asarray(counts, tiles.dtype)
    if tiles.ndim != 3 or tiles.shape[0] != n_shards:
        raise ValueError(
            f"tiles must be (n_shards={n_shards}, C, d_block), got "
            f"{tiles.shape}"
        )
    if counts.shape != (n_shards,):
        raise ValueError(f"counts must be ({n_shards},), got {counts.shape}")
    n_chunks = tiles.shape[1]
    pad = (-n_chunks) % n_shards

    def local_fn(tile, cnt):
        contrib = cnt[0] * tile[0]  # (C, d_block), this shard's weighted row
        if pad:
            contrib = jnp.pad(contrib, ((0, pad), (0, 0)))
        part = jax.lax.psum_scatter(contrib, axis, scatter_dimension=0,
                                    tiled=True)
        total = jax.lax.psum(cnt[0], axis)
        full = jax.lax.all_gather(part / total, axis, axis=0, tiled=True)
        return full[:n_chunks]

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis)),
        out_specs=P(None, None), check_vma=False,
    )(tiles, counts)
