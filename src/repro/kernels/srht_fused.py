"""Pallas TPU kernels: fused SRHT encode/decode batched over (clients x chunks).

These kernels close the decode gap for the paper's headline estimator
(`rand_proj_spatial`): the server-side inverse SRHT

    y_c = sum_i G_i^T z_ic,      G_i = (1/sqrt(d)) E_i H D_i

used to run as a per-chunk Python loop over unfused scatter + FWHT + sign
multiplies. Here the whole reduction is ONE kernel launch:

  * `fwht_rowsigns_pallas`   — encode-side mirror fusion: per-row Rademacher
    sign flip + FWHT (+ optional post-signs) in one VMEM-resident pass. The
    coordinate subsample (E_i gather) stays in XLA where it fuses with the
    payload pack.
  * `srht_decode_sum_pallas` — inverse-SRHT + sign/scale + scatter-add over
    clients. Grid is (chunk_tiles, n_clients) with the CLIENT axis rightmost
    (fastest-varying), so each output tile is visited by all n clients
    consecutively and accumulated in place (`@pl.when(i == 0)` initialises).
  * `srht_gram_apply_pallas` — matrix-free S v = sum_i G_i^T G_i v: two FWHTs
    with a coordinate mask between them, same accumulation scheme. This is the
    inner product of the fused decode's conjugate-gradient resolvent solve
    (docs/DESIGN.md §3.5).

All three reuse the Kronecker-factored MXU tiling of `kernels/fwht.py`
(H_d = H_a (x) H_b, two dot_generals against tiny +-1 constants). Unlike
`fwht_pallas`, the 1/sqrt(d) scale is NOT folded into the H_b constant but
applied as an explicit elementwise multiply after the transform — exactly
where `kernels/ref.py` applies it — so interpret mode is bit-exact against
the oracle composition (see the golden tests in tests/test_kernels.py).

VMEM budget: Pallas double-buffers every blocked operand, so a kernel with
m (block_chunks, d) operands (per-chunk inputs plus the output) holds 2m
such tiles, plus the (a, a), (b, b) Hadamard constants and the kernel
body's own temporaries. Each wrapper tells `fwht._pick_block_rows` its m,
which sizes the tiles so the 2m of them stay inside 8 MiB, half of the v5e's
16 MiB scoped VMEM limit (at d = 1024: 512 rows for m = 2, 256 for m = 3 or
4). Shared (1, d) sign or mask rows are not counted. See docs/KERNELS.md for
the worked walkthrough.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref as _ref
from .fwht import _fwht_tile, _pick_block_rows, _split_dims


def _rowsigns_kernel(
    h_a_ref, h_b_ref, s_ref, x_ref, o_ref,
    *, a: int, b: int, sign_pre: bool, sign_post: bool, scale: float,
):
    x = x_ref[...].astype(jnp.float32)  # (bt, d)
    s = s_ref[...].astype(jnp.float32)  # (bt, d) — one diagonal PER ROW
    if sign_pre:
        x = x * s
    t = _fwht_tile(x, h_a_ref, h_b_ref, a=a, b=b)
    if sign_post:
        t = t * s
    if scale != 1.0:
        t = t * jnp.float32(scale)
    o_ref[...] = t.astype(o_ref.dtype)


def _decode_sum_kernel(
    h_a_ref, h_b_ref, s_ref, u_ref, o_ref, *, a: int, b: int, scale: float
):
    i = pl.program_id(1)  # client index — rightmost grid axis, fastest-varying
    u = u_ref[0].astype(jnp.float32)          # (bt, d) scattered payloads
    t = _fwht_tile(u, h_a_ref, h_b_ref, a=a, b=b)
    t = t * s_ref[0].astype(jnp.float32)      # (bt, d) or broadcast (1, d)
    if scale != 1.0:
        t = t * jnp.float32(scale)
    t = t.astype(o_ref.dtype)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = t

    @pl.when(i > 0)
    def _acc():
        o_ref[...] += t


def _gram_apply_kernel(
    h_a_ref, h_b_ref, s_ref, m_ref, v_ref, o_ref, *, a: int, b: int, scale: float
):
    i = pl.program_id(1)
    v = v_ref[...].astype(jnp.float32)        # (bt, d) — same tile for every i
    s = s_ref[0].astype(jnp.float32)
    t = _fwht_tile(v * s, h_a_ref, h_b_ref, a=a, b=b)
    t = t * m_ref[0].astype(jnp.float32)      # keep only client i's coordinates
    t = _fwht_tile(t, h_a_ref, h_b_ref, a=a, b=b)
    t = t * s
    if scale != 1.0:
        t = t * jnp.float32(scale)
    t = t.astype(o_ref.dtype)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = t

    @pl.when(i > 0)
    def _acc():
        o_ref[...] += t


def _hadamard_consts(a: int, b: int):
    h_a = jnp.asarray(_ref.hadamard_matrix(a), jnp.float32)
    h_b = jnp.asarray(_ref.hadamard_matrix(b), jnp.float32)
    return h_a, h_b


def _pad_chunk_axis(x: jnp.ndarray, axis: int, to_multiple: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % to_multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("sign_pre", "sign_post", "scale", "block_rows", "interpret"),
)
def fwht_rowsigns_pallas(
    x: jnp.ndarray,
    signs: jnp.ndarray,
    *,
    sign_pre: bool = False,
    sign_post: bool = False,
    scale: float = 1.0,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused batched FWHT with per-row Rademacher diagonals.

    ``out = scale * [signs *] H_d ([signs *] x)`` with x, signs of shape
    (rows, d) — row r uses diagonal signs[r] (contrast `fwht_pallas`, which
    shares ONE diagonal across all rows). Oracle: ref.fwht_rowsigns_ref.
    """
    rows, d = x.shape
    a, b = _split_dims(d)
    bt = block_rows or _pick_block_rows(rows, d, n_tiles=3)
    x = _pad_chunk_axis(x, 0, bt)
    signs = _pad_chunk_axis(signs.astype(x.dtype), 0, bt)
    n_tiles = x.shape[0] // bt
    h_a, h_b = _hadamard_consts(a, b)

    out = pl.pallas_call(
        functools.partial(
            _rowsigns_kernel, a=a, b=b,
            sign_pre=sign_pre, sign_post=sign_post, scale=scale,
        ),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((a, a), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(h_a, h_b, signs, x)
    return out[:rows]


@functools.partial(jax.jit, static_argnames=("scale", "block_rows", "interpret"))
def srht_decode_sum_pallas(
    u: jnp.ndarray,
    signs: jnp.ndarray,
    *,
    scale: float,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused inverse-SRHT + sign/scale + scatter-add over clients.

    u:     (n, C, d) payloads already scattered to full width
    signs: (n, C, d) per-(client, chunk) diagonals, or (n, 1, d) when the
           chunk dimension shares one draw per client (shared_randomness)
    returns (C, d) = ``sum_i scale * signs_i * (H_d @ u_i)``.
    Oracle: ref.srht_decode_sum_ref (minus the scatter, done here by caller).
    """
    n, c, d = u.shape
    shared = signs.shape[1] == 1
    a, b = _split_dims(d)
    bt = block_rows or _pick_block_rows(c, d, n_tiles=2 if shared else 3)
    u = _pad_chunk_axis(u, 1, bt)
    if not shared:
        signs = _pad_chunk_axis(signs, 1, bt)
    n_ctiles = u.shape[1] // bt
    h_a, h_b = _hadamard_consts(a, b)

    if shared:
        s_spec = pl.BlockSpec((1, 1, d), lambda ct, i: (i, 0, 0))
    else:
        s_spec = pl.BlockSpec((1, bt, d), lambda ct, i: (i, ct, 0))

    out = pl.pallas_call(
        functools.partial(_decode_sum_kernel, a=a, b=b, scale=scale),
        grid=(n_ctiles, n),
        in_specs=[
            pl.BlockSpec((a, a), lambda ct, i: (0, 0)),
            pl.BlockSpec((b, b), lambda ct, i: (0, 0)),
            s_spec,
            pl.BlockSpec((1, bt, d), lambda ct, i: (i, ct, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda ct, i: (ct, 0)),
        out_shape=jax.ShapeDtypeStruct((u.shape[1], d), jnp.float32),
        interpret=interpret,
    )(h_a, h_b, signs.astype(jnp.float32), u.astype(jnp.float32))
    return out[:c]


@functools.partial(jax.jit, static_argnames=("scale", "block_rows", "interpret"))
def srht_gram_apply_pallas(
    v: jnp.ndarray,
    signs: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    scale: float,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused matrix-free ``S v = sum_i G_i^T G_i v`` for SRHT maps.

    v:     (C, d) one vector per chunk
    signs: (n, C, d) or (n, 1, d) Rademacher diagonals
    mask:  (n, C, d) or (n, 1, d) 0/1 indicators of each draw's rows
    scale: 1/d for G_i = (1/sqrt(d)) E_i H D_i
    returns (C, d). Oracle: ref.srht_gram_apply_ref.
    """
    c, d = v.shape
    n = signs.shape[0]
    a, b = _split_dims(d)
    n_tiles = 2 + (signs.shape[1] != 1) + (mask.shape[1] != 1)
    bt = block_rows or _pick_block_rows(c, d, n_tiles=n_tiles)
    v = _pad_chunk_axis(v, 0, bt)
    if signs.shape[1] != 1:
        signs = _pad_chunk_axis(signs, 1, bt)
    if mask.shape[1] != 1:
        mask = _pad_chunk_axis(mask, 1, bt)
    n_ctiles = v.shape[0] // bt
    h_a, h_b = _hadamard_consts(a, b)

    def _bc_spec(arr):
        if arr.shape[1] == 1:
            return pl.BlockSpec((1, 1, d), lambda ct, i: (i, 0, 0))
        return pl.BlockSpec((1, bt, d), lambda ct, i: (i, ct, 0))

    out = pl.pallas_call(
        functools.partial(_gram_apply_kernel, a=a, b=b, scale=scale),
        grid=(n_ctiles, n),
        in_specs=[
            pl.BlockSpec((a, a), lambda ct, i: (0, 0)),
            pl.BlockSpec((b, b), lambda ct, i: (0, 0)),
            _bc_spec(signs),
            _bc_spec(mask),
            pl.BlockSpec((bt, d), lambda ct, i: (ct, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda ct, i: (ct, 0)),
        out_shape=jax.ShapeDtypeStruct((v.shape[0], d), jnp.float32),
        interpret=interpret,
    )(h_a, h_b, signs.astype(jnp.float32), mask.astype(jnp.float32),
      v.astype(jnp.float32))
    return out[:c]
