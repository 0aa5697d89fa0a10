"""Pallas TPU kernel: flash attention (tiled online-softmax).

Motivation (docs/EXPERIMENTS.md §Roofline): the memory term of every attention
arch is dominated by the materialised (tokens x S x heads) score tensor —
XLA cannot keep it in VMEM across the matmul -> softmax -> matmul boundary,
and the pure-JAX kv-block scan still round-trips the f32 accumulator
through HBM once per kv block. This kernel keeps the (q_tile, dh)
accumulator and (q_tile, kv_tile) score tile resident in VMEM scratch for
the whole kv sweep: HBM traffic drops to Q/K/V reads + O writes, bounded
VMEM at any sequence length.

Grid: (batch*n_q_heads, q_tiles, kv_tiles) — kv innermost, revisiting the
same output block with carry state in VMEM scratch (the standard Pallas
flash pattern). GQA is handled in the K/V BlockSpec index maps
(kv head = q head // rep), so no K/V repeat is ever materialised. Causal /
sliding-window masks are arithmetic on absolute positions.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e9


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            n_kv: int, kv_tile: int, q_tile: int, window: int,
            q_offset: int, scale: float):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (qt, dh)
    qt = q.shape[0]
    q_pos = q_offset + i * q_tile + jax.lax.broadcasted_iota(jnp.int32, (qt, 1), 0)
    k_blk = k_ref[0].astype(jnp.float32)  # (kv_tile, dh)
    v_blk = v_ref[0].astype(jnp.float32)
    k_pos = j * kv_tile + jax.lax.broadcasted_iota(jnp.int32, (1, kv_tile), 1)

    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (qt, kv_tile)
    mask = k_pos <= q_pos
    if window > 0:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, _NEG)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * corr + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(j == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("rep", "window", "q_offset", "q_tile", "kv_tile", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,   # (N_q, Sq, dh)   N_q = batch * n_q_heads
    k: jnp.ndarray,   # (N_kv, Sk, dh)  N_kv = batch * n_kv_heads
    v: jnp.ndarray,
    *,
    rep: int,          # n_q_heads // n_kv_heads
    window: int = 0,
    q_offset: int = 0,
    q_tile: int = 128,
    kv_tile: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    nq, sq, dh = q.shape
    _, sk, _ = k.shape
    q_tile = min(q_tile, sq)
    kv_tile = min(kv_tile, sk)
    assert sq % q_tile == 0 and sk % kv_tile == 0, (sq, q_tile, sk, kv_tile)
    n_kv = sk // kv_tile
    grid = (nq, sq // q_tile, n_kv)

    return pl.pallas_call(
        functools.partial(
            _kernel, n_kv=n_kv, kv_tile=kv_tile, q_tile=q_tile,
            window=window, q_offset=q_offset, scale=1.0 / math.sqrt(dh),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_tile, dh), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, kv_tile, dh), lambda n, i, j, rep=rep: (n // rep, j, 0)),
            pl.BlockSpec((1, kv_tile, dh), lambda n, i, j, rep=rep: (n // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_tile, dh), lambda n, i, j: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_tile, 1), jnp.float32),
            pltpu.VMEM((q_tile, 1), jnp.float32),
            pltpu.VMEM((q_tile, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
