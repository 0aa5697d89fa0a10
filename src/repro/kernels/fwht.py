"""Pallas TPU kernel: batched Fast Walsh-Hadamard Transform (FWHT).

This is the compute hot-spot of the paper's SRHT encoding/decoding
(G_i = (1/sqrt(d)) E_i H D_i): every encode applies ``H @ (D_i x)`` and every
decode applies ``H @ scatter(payload)``.

TPU adaptation (see docs/DESIGN.md §3.2): instead of the classic log2(d)-stage
butterfly (VPU add/sub, memory-bound, one HBM round-trip per stage under XLA
fusion limits) we use the Kronecker factorisation of the Sylvester Hadamard
matrix

    H_d = H_a (x) H_b,        d = a*b,  b = min(d, 128)

so that the whole transform becomes two *matmuls* against tiny constant
+-1 matrices, executed on the MXU with the (rows, d) tile resident in VMEM:

    X   = x.reshape(rows*a, b)
    Y   = X @ H_b                      # lane-dim mix     (MXU, b=128 lanes)
    Z   = H_a @ Y.reshape(rows, a, b)  # sublane-dim mix  (MXU)
    out = Z.reshape(rows, d)

The reshape (rows, a*b) -> (rows*a, b) moves no data when b is a multiple of
the 128-lane width; the stage-2 contraction only permutes major dims. The
Rademacher sign flip (D_i) and the 1/sqrt(d) scale are fused into the kernel
(signs multiply on load; scale folded into the H_b constant), so an SRHT
encode is a single VMEM-resident pass over the data.

Validated against the pure-jnp oracle (kernels/ref.py) in interpret mode on
CPU; on TPU the same kernel lowers via Mosaic. ``interpret`` defaults to
False: a caller that wants the interpreter says so.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref as _ref


# Mosaic rounds float32 matmul operands to bfloat16 by default: on a v5e the
# transform then misses the oracle by a relative 3e-3, enough to stall the
# decode's CG solve. HIGHEST keeps the products exact in float32.
_PRECISION = jax.lax.Precision.HIGHEST


def _fwht_tile(x, h_a_ref, h_b_ref, *, a: int, b: int):
    """H_a (x) H_b applied to each row of a (bt, d) tile, d = a*b."""
    bt = x.shape[0]
    # stage 1: mix within contiguous groups of b (lane dimension).
    xg = x.reshape(bt * a, b)
    y = jax.lax.dot_general(
        xg, h_b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=_PRECISION,
        preferred_element_type=jnp.float32,
    )  # (bt*a, b); H_b symmetric so X @ H_b == X @ H_b^T
    if a == 1:
        return y.reshape(bt, b)
    # stage 2: mix across the a groups (sublane dimension).
    y3 = y.reshape(bt, a, b)
    z = jax.lax.dot_general(
        h_a_ref[...], y3,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_PRECISION,
        preferred_element_type=jnp.float32,
    )  # (a, bt, b)
    return z.transpose(1, 0, 2).reshape(bt, a * b)


def _kernel(h_a_ref, h_b_ref, s_ref, x_ref, o_ref, *, a: int, b: int, with_signs: bool):
    x = x_ref[...].astype(jnp.float32)  # (bt, d)
    if with_signs:
        x = x * s_ref[...].astype(jnp.float32)  # (1, d) broadcast over rows
    o_ref[...] = _fwht_tile(x, h_a_ref, h_b_ref, a=a, b=b).astype(o_ref.dtype)


def _split_dims(d: int) -> tuple[int, int]:
    if d & (d - 1) != 0 or d < 2:
        raise ValueError(f"FWHT dim must be a power of two >= 2, got {d}")
    b = min(d, 128)
    return d // b, b


# Bytes of VMEM the blocked (bt, d) operands may take, double-buffered: half
# of a TPU v5e core's 16 MiB scoped VMEM limit. The other half holds the
# kernel body's own (bt, d) temporaries (the two Kronecker stages and, at
# HIGHEST precision, the bfloat16 splits of their operands) and the Hadamard
# constants. At 12 MiB the three-operand kernels overran the limit by 48 KiB.
_VMEM_TILE_BUDGET = 8 * 1024 * 1024


def _pick_block_rows(n_rows: int, d: int, n_tiles: int = 2) -> int:
    """Tile height bt for a kernel with ``n_tiles`` blocked (bt, d) operands
    (inputs and output). Pallas double-buffers every blocked operand, so the
    kernel holds ``2 * n_tiles`` tiles of bt*d float32 at once; bt is the
    largest power of two that keeps them inside ``_VMEM_TILE_BUDGET``,
    floored at 8 rows and capped at max(8, n_rows) (a block as tall as the
    whole, padded array is always legal)."""
    per_row = 2 * n_tiles * d * 4
    bt = max(8, _VMEM_TILE_BUDGET // per_row)
    bt = 1 << (bt.bit_length() - 1)  # round down to power of two
    return int(min(bt, max(8, n_rows)))


@functools.partial(
    jax.jit, static_argnames=("with_signs", "scale", "block_rows", "interpret")
)
def fwht_pallas(
    x: jnp.ndarray,
    signs: jnp.ndarray | None = None,
    *,
    with_signs: bool = False,
    scale: float = 1.0,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched FWHT over the last axis: ``scale * H_d @ (signs? * x)``.

    x:     (rows, d), d a power of two (>=2); rows arbitrary (padded to tile).
    signs: optional (d,) +-1 Rademacher diagonal, fused on load.
    scale: constant folded into the H_b stage (e.g. 1/sqrt(d) for SRHT).
    """
    rows, d = x.shape
    a, b = _split_dims(d)
    bt = block_rows or _pick_block_rows(rows, d)
    pad = (-rows) % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_tiles = x.shape[0] // bt

    h_a = jnp.asarray(_ref.hadamard_matrix(a), jnp.float32)
    h_b = jnp.asarray(_ref.hadamard_matrix(b) * scale, jnp.float32)
    if signs is None:
        signs2 = jnp.ones((1, d), jnp.float32)
    else:
        signs2 = signs.reshape(1, d).astype(jnp.float32)

    out = pl.pallas_call(
        functools.partial(_kernel, a=a, b=b, with_signs=with_signs and signs is not None),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((a, a), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(h_a, h_b, signs2, x)
    if pad:
        out = out[:rows]
    return out
