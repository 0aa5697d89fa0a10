"""Pallas TPU kernels: row-wise coordinate selection and its adjoint.

The exchange moves k sampled coordinates of every (client, chunk) row of
width d: the encode gathers them, the decode scatters them back to full
width. Each row has its own index set, and XLA lowers such a minor-axis
gather or scatter to per-element work on the TPU (hundreds of times its HBM
roofline at the DME step's 4 x 126k x 1024). Here both are row tiles in
VMEM compared against a lane iota, one selected slot at a time:

  * `select_coords_pallas` — ``out[r, j] = t[r, rows[r, j]]``: for slot j,
    keep the one lane of each row whose index equals ``rows[r, j]`` and
    reduce the row to it.
  * `expand_coords_pallas` — ``out[r, rows[r, j]] = z[r, j]``, zero
    elsewhere: for slot j, select ``z[r, j]`` into the lane it names.

Values are copied, never computed. The select reduces the int32 bit
patterns with a max over a row that holds INT32_MIN everywhere but the
chosen lane, which returns those bits whatever they are (-0.0, inf, NaN,
subnormals); a float masked sum would turn -0.0 into +0.0. The expand is a
chain of selects into a +0.0 row, which is what ``zeros.at[rows].set(z)``
gives when a row's indices are distinct, as every caller's draw makes them.

A grid step loads a tile of ``bt`` rows and walks it eight rows (one
sublane group) at a time, so that the unrolled k-slot loop works on a few
vregs and not on the whole tile. The grid is ``cdiv(rows, bt)``: Pallas
masks the last, partial block, so no operand is padded. Tiles follow the
VMEM rule of `kernels/srht_fused.py` with m = 3 blocked operands (values,
indices, output; the k-wide ones counted as full width). See
docs/KERNELS.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fwht import _pick_block_rows

_SUB = 8  # rows per inner step: one sublane group
_N_TILES = 3  # blocked operands for the VMEM rule: values, indices, output
_INT32_MIN = jnp.iinfo(jnp.int32).min


def tile_rows(d: int) -> int:
    """The full tile height of both kernels at row width d."""
    return _pick_block_rows(1 << 30, d, n_tiles=_N_TILES)


def _block_rows(n_rows: int, d: int, block_rows: int | None) -> int:
    bt = block_rows or tile_rows(d)
    return min(bt, -(-n_rows // _SUB) * _SUB)


def _select_kernel(t_ref, rows_ref, o_ref):
    bt, d = t_ref.shape
    k = rows_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, d), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (_SUB, k), 1)

    def step(s, carry):
        r0 = pl.multiple_of(s * _SUB, _SUB)
        bits = jax.lax.bitcast_convert_type(t_ref[pl.ds(r0, _SUB), :], jnp.int32)
        idx = rows_ref[pl.ds(r0, _SUB), :]
        out = jnp.zeros((_SUB, k), jnp.int32)
        for j in range(k):
            hit = lane == idx[:, j:j + 1]
            v = jnp.max(jnp.where(hit, bits, _INT32_MIN), axis=1, keepdims=True)
            out = jnp.where(slot == j, v, out)
        o_ref[pl.ds(r0, _SUB), :] = jax.lax.bitcast_convert_type(out, o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bt // _SUB, step, 0)


def _expand_kernel(*refs, fill):
    z_ref, rows_ref, o_ref = refs if fill is None else (None, *refs)
    bt, d = o_ref.shape
    k = rows_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, d), 1)

    def step(s, carry):
        r0 = pl.multiple_of(s * _SUB, _SUB)
        idx = rows_ref[pl.ds(r0, _SUB), :]
        z = None if z_ref is None else z_ref[pl.ds(r0, _SUB), :]
        out = jnp.zeros((_SUB, d), o_ref.dtype)
        for j in range(k):
            zj = jnp.asarray(fill, o_ref.dtype) if z is None else z[:, j:j + 1]
            out = jnp.where(lane == idx[:, j:j + 1], zj, out)
        o_ref[pl.ds(r0, _SUB), :] = out
        return carry

    jax.lax.fori_loop(0, bt // _SUB, step, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def select_coords_pallas(
    t: jnp.ndarray,
    rows: jnp.ndarray,
    *,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[r, j] = t[r, rows[r, j]]``; t: (R, d) of a 4-byte dtype, rows:
    (R, k) int32 in [0, d). -> (R, k). Oracle: ``jnp.take_along_axis``."""
    n, d = t.shape
    k = rows.shape[1]
    bt = _block_rows(n, d, block_rows)
    return pl.pallas_call(
        _select_kernel,
        grid=(pl.cdiv(n, bt),),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), t.dtype),
        interpret=interpret,
    )(t, rows.astype(jnp.int32))


@functools.partial(
    jax.jit, static_argnames=("d", "fill", "block_rows", "interpret")
)
def expand_coords_pallas(
    z: jnp.ndarray | None,
    rows: jnp.ndarray,
    d: int,
    *,
    fill: float | None = None,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[r, rows[r, j]] = z[r, j]``, +0.0 elsewhere; z: (R, k) of a
    4-byte dtype, or None with ``fill`` the float32 value every selected
    coordinate takes; rows: (R, k) int32, distinct within a row.
    -> (R, d). Oracle: ``ref.srht_scatter_ref``."""
    n, k = rows.shape
    bt = _block_rows(n, d, block_rows)
    narrow = pl.BlockSpec((bt, k), lambda i: (i, 0))
    operands = (rows.astype(jnp.int32),) if z is None else (z, rows.astype(jnp.int32))
    return pl.pallas_call(
        functools.partial(_expand_kernel, fill=fill),
        grid=(pl.cdiv(n, bt),),
        in_specs=[narrow] * len(operands),
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32 if z is None else z.dtype),
        interpret=interpret,
    )(*operands)
