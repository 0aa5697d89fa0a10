"""Jit'd public wrappers around the Pallas kernels, with CPU fallbacks.

``fwht``        - batched Walsh-Hadamard transform over the last axis.
``srht_encode`` - fused SRHT encode:  (1/sqrt(d)) (H (signs*x))[rows].
``srht_decode`` - SRHT adjoint:       (1/sqrt(d)) signs * (H scatter(u)).

Fused batched ops behind the rand_proj_spatial fast path (docs/KERNELS.md):

``srht_encode_batch`` - encode with one independent draw per (client, chunk).
``srht_decode_sum``   - y_c = sum_i G_i^T z_ic in one launch.
``srht_gram_apply``   - matrix-free S v = sum_i G_i^T G_i v (CG inner apply).

Row-wise coordinate selection, shared by Rand-Proj-Spatial and Rand-k
(kernels/select.py):

``select_coords`` - out[..r.., j] = t[..r.., rows[..r.., j]] (the encode's gather).
``expand_coords`` - its adjoint: the k values back at full width, zero elsewhere.

On TPU the Pallas kernel is used (compiled); elsewhere the same kernel body
runs in interpret mode, or the pure-jnp oracle for tiny shapes where the
interpreter overhead dominates. The oracle (kernels/ref.py) is the
correctness contract; tests assert allclose across shape/dtype sweeps.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from .fwht import fwht_pallas
from ..obs import record_dispatch as _record_dispatch

# interpret-mode execution is pure-python per grid step; for the small chunk
# sizes used on CPU the vectorised oracle is much faster. The Pallas path is
# still exercised (interpret=True) by tests and by `use_pallas="force"`.
_PALLAS_MIN_ELEMS = 1 << 22


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _should_use_pallas(
    size: int, use_pallas: str | bool, tile: int | None = None
) -> tuple[bool, bool]:
    """-> (use_kernel, interpret).

    ``size`` counts elements; with ``tile`` it counts rows, and ``tile`` is
    the rows of one full kernel tile. That is the row-wise select/expand
    pair: on TPU it takes the kernel from one full tile of rows, and XLA's
    gather/scatter below it (where a grid step's overhead buys nothing) and
    off the TPU."""
    if use_pallas == "force":
        return True, not _on_tpu()
    if use_pallas == "never" or use_pallas is False:
        return False, False
    if tile is not None:
        return _on_tpu() and size >= tile, False
    if _on_tpu():
        return True, False
    return size >= _PALLAS_MIN_ELEMS, True


def _dispatch(
    op: str, size: int, use_pallas: str | bool, tile: int | None = None
) -> tuple[bool, bool]:
    """``_should_use_pallas`` + one telemetry count per decision.

    The decision is a Python static, so under jit it records at trace time —
    i.e. once per compilation, which is exactly the granularity at which the
    route is actually chosen."""
    use, interp = _should_use_pallas(size, use_pallas, tile)
    _record_dispatch(op, use, interp)
    return use, interp


def fwht(x: jnp.ndarray, *, scale: float = 1.0, use_pallas: str | bool = "auto") -> jnp.ndarray:
    """``scale * H_d @ x`` along the last axis; x: (..., d)."""
    d = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, d)
    use, interp = _dispatch("fwht", x2.size, use_pallas)
    if use:
        out = fwht_pallas(x2, with_signs=False, scale=scale, interpret=interp)
    else:
        out = _ref.fwht_ref(x2)
        if scale != 1.0:
            out = out * jnp.asarray(scale, out.dtype)
    return out.reshape(*lead, d)


def srht_encode(
    x: jnp.ndarray,
    signs: jnp.ndarray,
    rows: jnp.ndarray,
    *,
    use_pallas: str | bool = "auto",
) -> jnp.ndarray:
    """Fused SRHT encode ``G x = (1/sqrt(d)) (H (signs * x))[rows]``.

    x: (..., d); signs: (d,); rows: (k,) int32. -> (..., k)
    The sign-multiply and 1/sqrt(d) scale are fused into the kernel; the
    row-gather stays in XLA (cheap, k << d).
    """
    d = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, d)
    use, interp = _dispatch("srht_encode", x2.size, use_pallas)
    inv = 1.0 / math.sqrt(d)
    if use:
        t = fwht_pallas(x2, signs, with_signs=True, scale=inv, interpret=interp)
    else:
        t = _ref.fwht_ref(x2 * signs) * jnp.asarray(inv, x2.dtype)
    out = jnp.take(t, rows, axis=-1)
    return out.reshape(*lead, rows.shape[0])


def srht_decode(
    u: jnp.ndarray,
    signs: jnp.ndarray,
    rows: jnp.ndarray,
    d: int,
    *,
    use_pallas: str | bool = "auto",
) -> jnp.ndarray:
    """SRHT adjoint ``G^T u = (1/sqrt(d)) signs * (H scatter_rows(u))``.

    u: (..., k) -> (..., d). H is symmetric so H^T == H.
    """
    k = u.shape[-1]
    lead = u.shape[:-1]
    u2 = u.reshape(-1, k)
    full = jnp.zeros((u2.shape[0], d), u2.dtype)
    full = full.at[:, rows].set(u2)
    use, interp = _dispatch("srht_decode", full.size, use_pallas)
    inv = 1.0 / math.sqrt(d)
    if use:
        t = fwht_pallas(full, with_signs=False, scale=inv, interpret=interp)
        out = t * signs
    else:
        out = _ref.fwht_ref(full) * (signs * jnp.asarray(inv, u2.dtype))
    return out.reshape(*lead, d)


def flash_attention(q, k, v, *, rep: int, window: int = 0, q_offset: int = 0,
                    q_tile: int = 128, kv_tile: int = 128,
                    use_pallas: str | bool = "auto"):
    """Tiled flash attention; q (N_q, Sq, dh), k/v (N_kv, Sk, dh).

    Pallas kernel on TPU; oracle elsewhere (interpret mode is exercised by
    tests — running it for real workloads on CPU is interpreter-bound).
    """
    from .flash_attention import flash_attention_pallas

    use, interp = _dispatch("flash_attention", q.size, use_pallas)
    if use_pallas == "force" or (use and _on_tpu()):
        return flash_attention_pallas(
            q, k, v, rep=rep, window=window, q_offset=q_offset,
            q_tile=q_tile, kv_tile=kv_tile, interpret=interp,
        )
    return _ref.flash_attention_ref(q, k, v, rep=rep, window=window, q_offset=q_offset)


def srht_encode_batch(
    x: jnp.ndarray,
    signs: jnp.ndarray,
    rows: jnp.ndarray,
    *,
    use_pallas: str | bool = "auto",
) -> jnp.ndarray:
    """Fused batched SRHT encode with PER-ROW draws.

    ``out[..r..] = (1/sqrt(d)) (H (signs[..r..] * x[..r..]))[rows[..r..]]``

    x, signs: (..., d); rows: (..., k) int32 — leading dims aligned, one
    independent draw per leading index (the non-shared-randomness encode,
    batched over clients x chunks). Contrast `srht_encode`, which shares one
    (signs, rows) draw across the whole batch.
    """
    from .srht_fused import fwht_rowsigns_pallas

    d = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, d)
    s2 = jnp.broadcast_to(signs, x.shape).reshape(-1, d)
    use, interp = _dispatch("srht_encode_batch", x2.size, use_pallas)
    inv = 1.0 / math.sqrt(d)
    if use:
        t = fwht_rowsigns_pallas(x2, s2, sign_pre=True, scale=inv, interpret=interp)
    else:
        t = _ref.fwht_rowsigns_ref(x2, s2, sign_pre=True, scale=inv)
    return select_coords(t.reshape(*lead, d), rows, use_pallas=use_pallas)


def srht_decode_sum(
    z: jnp.ndarray,
    signs: jnp.ndarray,
    rows: jnp.ndarray,
    d: int,
    *,
    use_pallas: str | bool = "auto",
) -> jnp.ndarray:
    """Fused client-summed SRHT adjoint ``y_c = sum_i G_i^T z_ic``.

    z: (n, C, k); signs: (n, C|1, d); rows: (n, C|1, k) — the middle axis is 1
    when clients share one draw across chunks (shared_randomness). -> (C, d)

    The scatter to full width is ``expand_coords``; the FWHT + sign/scale +
    scatter-add over clients is one Pallas launch batched over
    (clients x chunks).
    """
    from .srht_fused import srht_decode_sum_pallas

    full = expand_coords(z, rows, d, use_pallas=use_pallas)  # (n, C, d)
    use, interp = _dispatch("srht_decode_sum", full.size, use_pallas)
    inv = 1.0 / math.sqrt(d)
    if use:
        return srht_decode_sum_pallas(full, signs, scale=inv, interpret=interp)
    out = _ref.fwht_rowsigns_ref(full, signs, sign_post=True, scale=inv)
    return jnp.sum(out, axis=0)


def srht_gram_apply(
    v: jnp.ndarray,
    signs: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    use_pallas: str | bool = "auto",
) -> jnp.ndarray:
    """Fused matrix-free ``S v = sum_i G_i^T G_i v`` for SRHT maps.

    v: (C, d); signs, mask: (n, C|1, d) with mask the 0/1 row indicator of
    each draw. Two FWHTs with a coordinate mask between them — the CG inner
    apply of the fused decode (docs/DESIGN.md §3.5). -> (C, d)
    """
    from .srht_fused import srht_gram_apply_pallas

    n = signs.shape[0]
    d = v.shape[-1]
    use, interp = _dispatch("srht_gram_apply", n * v.shape[0] * d, use_pallas)
    if use:
        return srht_gram_apply_pallas(v, signs, mask, scale=1.0 / d, interpret=interp)
    return _ref.srht_gram_apply_ref(v, signs, mask)


def _row_kernel_route(dtype, use_pallas: str | bool) -> str | bool:
    """The select/expand kernels move 4-byte values; other dtypes stay on XLA."""
    return use_pallas if jnp.dtype(dtype).itemsize == 4 else "never"


def select_coords(
    t: jnp.ndarray, rows: jnp.ndarray, *, use_pallas: str | bool = "auto"
) -> jnp.ndarray:
    """Row-wise gather ``jnp.take_along_axis(t, rows, -1)``, bit for bit.

    t: (..., d); rows: (..., k) int32 in [0, d), leading dims broadcastable
    to t's. -> (..., k). Works under vmap (the per-client encode)."""
    from .select import select_coords_pallas, tile_rows

    d, k = t.shape[-1], rows.shape[-1]
    lead = t.shape[:-1]
    rows = jnp.broadcast_to(rows, lead + (k,))
    use, interp = _dispatch(
        "select_coords", math.prod(lead), _row_kernel_route(t.dtype, use_pallas),
        tile=tile_rows(d),
    )
    if not use:
        return jnp.take_along_axis(t, rows, axis=-1)
    out = select_coords_pallas(t.reshape(-1, d), rows.reshape(-1, k), interpret=interp)
    return out.reshape(*lead, k)


def expand_coords(
    z: jnp.ndarray | float, rows: jnp.ndarray, d: int, *,
    use_pallas: str | bool = "auto",
) -> jnp.ndarray:
    """Row-wise scatter ``zeros(d).at[rows].set(z)`` per row, bit for bit.

    z: (..., k), or a Python float that every selected coordinate takes (the
    0/1 mask, hit counts: no (..., k) array of it is made); rows: (..., k)
    int32, DISTINCT within a row (every caller's draw is without
    replacement), leading dims broadcastable to z's. -> (..., d), float32
    for a float z."""
    from .select import expand_coords_pallas, tile_rows

    fill = None
    if isinstance(z, float):
        fill, z = float(z), None
    lead = rows.shape[:-1] if z is None else z.shape[:-1]
    k = rows.shape[-1]
    rows = jnp.broadcast_to(rows, lead + (k,))
    dtype = jnp.float32 if z is None else z.dtype
    use, interp = _dispatch(
        "expand_coords", math.prod(lead), _row_kernel_route(dtype, use_pallas),
        tile=tile_rows(d),
    )
    if not use:
        vals = jnp.full(rows.shape, fill, dtype) if z is None else z
        return _ref.srht_scatter_ref(vals, rows, d)
    out = expand_coords_pallas(
        None if z is None else z.reshape(-1, k), rows.reshape(-1, k), d,
        fill=fill, interpret=interp,
    )
    return out.reshape(*lead, d)


# ------------------------------------------------- very-sparse projection ops
# The SparseProj codec's hot ops (core/estimators/sparse_proj.py). These are
# gather/scatter bound with O(k * nnz) work per chunk — there is no FWHT-like
# dense structure for a Pallas kernel to fuse, and XLA already fuses the
# gather+reduce / scatter-add, so the dispatch is pinned to the XLA path
# (use_pallas="never"). They still route through ``_dispatch`` so the kernel
# telemetry (repro.obs) records the decision at trace time like every other
# op, and a future Pallas lowering slots in without touching callers.


def sparse_proj_encode(x: jnp.ndarray, signs: jnp.ndarray, cols: jnp.ndarray) -> jnp.ndarray:
    """Very-sparse projection encode ``G x``, G rows = nnz signed entries of
    magnitude 1/sqrt(nnz) at key-derived columns (unit-norm rows).

    x: (..., d); signs, cols: (..., k, nnz) broadcast-aligned. -> (..., k)
    O(k * nnz) flops per vector vs the SRHT's O(d log d).
    """
    nnz = cols.shape[-1]
    _dispatch("sparse_proj_encode", x.size, "never")
    out = _ref.sparse_encode_ref(x, signs, cols)
    return out * jnp.asarray(1.0 / math.sqrt(nnz), out.dtype)


def sparse_proj_adjoint(
    z: jnp.ndarray, signs: jnp.ndarray, cols: jnp.ndarray, d: int
) -> jnp.ndarray:
    """Sparse adjoint ``G^T z`` per leading index (no client sum — the decode
    keeps the per-client scatters for its pooled R-hat statistic).

    z: (..., k); signs, cols: (..., k, nnz) broadcast-aligned (the decode
    passes (n, C, k) values with (n, C|1, k, nnz) draws). -> (..., d)
    """
    _dispatch("sparse_proj_adjoint", z.size, "never")
    out = _ref.sparse_scatter_add_ref(z, signs, cols, d)
    nnz = cols.shape[-1]
    return out * jnp.asarray(1.0 / math.sqrt(nnz), out.dtype)


def sparse_proj_gram_apply(
    v: jnp.ndarray, signs: jnp.ndarray, cols: jnp.ndarray
) -> jnp.ndarray:
    """Matrix-free ``S v = sum_i G_i^T G_i v`` for sparse maps — the CG inner
    apply of the SparseProj resolvent decode.

    v: (C, d); signs, cols: (n, C|1, k, nnz). -> (C, d)
    """
    n = signs.shape[0]
    _dispatch("sparse_proj_gram_apply", n * v.size, "never")
    nnz = cols.shape[-1]
    out = _ref.sparse_gram_apply_ref(v, signs, cols)
    return out * jnp.asarray(1.0 / nnz, out.dtype)


def srht_rows_matrix(signs: jnp.ndarray, rows: jnp.ndarray, d: int) -> jnp.ndarray:
    """Materialise G = (1/sqrt(d)) E H D as a (k, d) matrix.

    Used by the Gram-trick decode (docs/DESIGN.md §3.3) where A = stack(G_i) is
    fed to MXU matmuls. Row r of E H D is H[rows[r], :] * signs.
    """
    h = jnp.asarray(_ref.hadamard_matrix(d), jnp.float32)
    return (h[rows, :] * signs[None, :]) * (1.0 / np.sqrt(d))
