"""Blockwise application of DME estimators to framework-scale vectors.

The paper analyses a single d-dimensional vector; a model gradient has
d ~ 1e9. We flatten the gradient pytree, zero-pad to a multiple of
``d_block`` (a power of two, so SRHT applies per block), and run the
estimator vmapped/batched over chunks. All of the paper's per-vector
guarantees (unbiasedness, MSE) hold per chunk; MSE adds across chunks.
See docs/DESIGN.md §3.1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


# A TPU lays out the two minor dims of an array in (8, 128) tiles. Between a
# flat vector and a chunk count C that is not a multiple of 8, the TPU
# compiler emits code in proportion to the array: at a 129M-parameter
# gradient, hundreds of MB of program and ten minutes of compile. Reshaping
# at C rounded up to the tile and slicing the pad rows off costs neither.
_TILE_ROWS = 8


def num_chunks(d_flat: int, d_block: int) -> int:
    return -(-d_flat // d_block)


def _tile_rows(c: int) -> int:
    return -(-c // _TILE_ROWS) * _TILE_ROWS


def chunk(x: jnp.ndarray, d_block: int) -> jnp.ndarray:
    """(d_flat,) -> (C, d_block), zero-padding the tail."""
    (d_flat,) = x.shape
    c = num_chunks(d_flat, d_block)
    rows = _tile_rows(c)
    x = jnp.pad(x, (0, rows * d_block - d_flat))
    return x.reshape(rows, d_block)[:c]


def unchunk(xc: jnp.ndarray, d_flat: int) -> jnp.ndarray:
    """(C, d_block) -> (d_flat,), dropping pad."""
    c = xc.shape[0]
    xc = jnp.pad(xc, ((0, _tile_rows(c) - c), (0, 0)))
    return xc.reshape(-1)[:d_flat]


def flatten_tree(tree):
    """pytree -> (flat (d,), unravel_fn). Thin wrapper for a stable import point."""
    flat, unravel = ravel_pytree(tree)
    return flat, unravel


def tree_chunk(tree, d_block: int):
    """pytree -> ((C, d_block) chunks, restore_fn)."""
    flat, unravel = ravel_pytree(tree)
    d_flat = flat.shape[0]
    xc = chunk(flat, d_block)

    def restore(xc_hat: jnp.ndarray):
        return unravel(unchunk(xc_hat, d_flat))

    return xc, restore
