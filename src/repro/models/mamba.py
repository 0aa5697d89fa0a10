"""Mamba2 (SSD — state-space duality) block, pure JAX.

Training/prefill uses the chunked SSD algorithm (arXiv:2405.21060): within a
chunk the recurrence is materialised as a decay-masked attention-like matmul
(MXU-friendly quadratic-in-Q work), across chunks a lax.scan carries the
(heads, headdim, state) recurrent state. Decode is the O(1) recurrence.

Layout: in_proj -> [z (gate), x, B, C, dt]; short causal conv over (x,B,C);
SSD; gated RMSNorm; out_proj. Jamba's Mamba-1 layers are realised with this
SSD block (state=16, heads=d_inner/headdim) — a documented simplification
(docs/DESIGN.md §7): identical interface, shapes and asymptotics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers


def _split_proj(cfg, zxbcdt):
    di, g, n, nh = cfg.mamba_d_inner, cfg.mamba_ngroups, cfg.d_state, cfg.mamba_heads
    z, x, bb, cc, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + g * n, 2 * di + 2 * g * n], axis=-1
    )
    return z, x, bb, cc, dt  # dt: (..., nh)


def _conv_train(xbc, w, b):
    """Causal depthwise conv along seq. xbc: (B, S, C); w: (C, K)."""
    k = w.shape[-1]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    # depthwise: feature_group_count = C
    out = jax.lax.conv_general_dilated(
        pad.astype(jnp.float32),
        w.T[:, None, :].astype(jnp.float32),  # (K, 1, C) -> spec OIW? use dimension_numbers
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=xbc.shape[-1],
    )
    return jax.nn.silu(out + b.astype(jnp.float32)).astype(xbc.dtype)


def ssd_chunked(x, dt, a_log, bb, cc, dd, chunk: int, unroll=1):
    """Chunked SSD scan.

    x:  (B, L, H, P)   inputs per head
    dt: (B, L, H)      positive step sizes (post-softplus)
    a_log: (H,)        log(-A)
    bb, cc: (B, L, H, N)  input/output projections (groups pre-broadcast)
    dd: (H,)           skip
    -> y (B, L, H, P)
    """
    b, l, h, p = x.shape
    n = bb.shape[-1]
    q = min(chunk, l)
    nc = -(-l // q)
    pad = nc * q - l
    if pad:
        x, dt, bb, cc = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)) for t in (x, dt, bb, cc))

    f32 = jnp.float32
    xr = x.reshape(b, nc, q, h, p).astype(f32)
    dtr = dt.reshape(b, nc, q, h).astype(f32)
    br = bb.reshape(b, nc, q, h, n).astype(f32)
    cr = cc.reshape(b, nc, q, h, n).astype(f32)

    da = -jnp.exp(a_log.astype(f32)) * dtr  # (b, nc, q, h) log-decay per step
    cs = jnp.cumsum(da, axis=2)  # inclusive cumsum
    xdt = xr * dtr[..., None]

    # intra-chunk: y_q += C_q . sum_{k<=q} exp(cs_q - cs_k) dt_k B_k x_k
    # decay: (b, nc, h, q, k). The causal mask goes on the exponent, not on
    # exp(): above the diagonal the exponent is positive, exp overflows at a
    # full 256-step chunk, and masking inf after the fact makes the backward
    # pass NaN (0 * inf). Kept entries have exponents <= 0 (stable).
    csh = cs.transpose(0, 1, 3, 2)
    mask = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(mask[None, None, None],
                              csh[:, :, :, :, None] - csh[:, :, :, None, :],
                              -jnp.inf))
    scores = jnp.einsum("bcqhn,bckhn->bchqk", cr, br) * decay
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores, xdt)

    # chunk-end states: S_c = sum_k exp(cs_Q - cs_k) B_k (dt_k x_k)^T
    end_decay = jnp.exp(cs[:, :, -1:, :] - cs)  # (b, nc, q, h)
    states = jnp.einsum("bckhn,bckh,bckhp->bchnp", br, end_decay, xdt)
    total = jnp.exp(cs[:, :, -1, :])  # (b, nc, h) chunk total decay

    def inter(h_carry, inp):
        s_c, tot = inp
        out = h_carry  # state at chunk START
        h_new = h_carry * tot[..., None, None] + s_c
        return h_new, out

    h0 = jnp.zeros((b, h, n, p), f32)
    h_final, h_prev = jax.lax.scan(
        inter, h0, (states.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2)),
        unroll=unroll,
    )
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)  # (b, nc, h, n, p)

    y_inter = jnp.einsum("bcqhn,bcqh,bchnp->bcqhp", cr, jnp.exp(cs), h_prev)
    y = (y_intra + y_inter).reshape(b, nc * q, h, p)[:, :l]
    y = y + x.reshape(b, nc * q, h, p)[:, :l].astype(f32) * dd.astype(f32)[None, None, :, None]
    return y, h_final


def mamba_layer(x, p, cfg, *, cache=None):
    """Mamba2 block with residual. Returns (y, new_cache).

    cache = {"conv": (B, K-1, convdim), "ssm": (B, H, N, P)} for decode.
    """
    b, s, _ = x.shape
    di, nh, hd = cfg.mamba_d_inner, cfg.mamba_heads, cfg.mamba_headdim
    g, n = cfg.mamba_ngroups, cfg.d_state
    xn = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    if cfg.mamba_split_proj:
        z = layers.dense(xn, p["in_z"])
        xi = layers.dense(xn, p["in_x"])
        bc = layers.dense(xn, p["in_bc"])
        bb, cc = jnp.split(bc, 2, axis=-1)
        dt = layers.dense(xn, p["in_dt"])
    else:
        zxbcdt = layers.dense(xn, p["in_proj"])
        z, xi, bb, cc, dt = _split_proj(cfg, zxbcdt)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    xbc = jnp.concatenate([xi, bb, cc], axis=-1)  # (B, S, convdim)
    if cache is None or s > 1:
        xbc_raw = xbc
        xbc = _conv_train(xbc, p["conv_w"], p["conv_b"])
        xi, bb, cc = jnp.split(xbc, [di, di + g * n], axis=-1)
        xh = xi.reshape(b, s, nh, hd)
        bh = jnp.repeat(bb.reshape(b, s, g, n), nh // g, axis=2)
        ch = jnp.repeat(cc.reshape(b, s, g, n), nh // g, axis=2)
        y, h_final = ssd_chunked(xh, dt, p["a_log"], bh, ch, p["d_skip"],
                                 cfg.mamba_chunk, unroll=True if cfg.force_unroll else 1)
        new_cache = None
        if cache is not None:
            # prefill: conv history = last (K-1) PRE-activation inputs
            kconv = p["conv_w"].shape[-1]
            hist = jnp.concatenate([cache["conv"], xbc_raw], axis=1)[:, -(kconv - 1):]
            new_cache = {"conv": hist, "ssm": h_final}
    else:
        # ---- O(1) recurrent decode (s == 1) -----------------------------
        kconv = p["conv_w"].shape[-1]
        hist = jnp.concatenate([cache["conv"], xbc], axis=1)  # (B, K, convdim)
        conv_out = jnp.einsum("bkc,ck->bc", hist.astype(jnp.float32), p["conv_w"].astype(jnp.float32))
        xbc1 = jax.nn.silu(conv_out + p["conv_b"].astype(jnp.float32)).astype(x.dtype)
        new_conv = hist[:, 1:]
        xi, bb, cc = jnp.split(xbc1, [di, di + g * n], axis=-1)
        xh = xi.reshape(b, nh, hd)
        bh = jnp.repeat(bb.reshape(b, g, n), nh // g, axis=1)
        ch = jnp.repeat(cc.reshape(b, g, n), nh // g, axis=1)
        dt1 = dt[:, 0]  # (B, H)
        da = jnp.exp(-jnp.exp(p["a_log"].astype(jnp.float32)) * dt1)  # (B, H)
        upd = jnp.einsum("bhn,bhp->bhnp", bh.astype(jnp.float32), xh.astype(jnp.float32) * dt1[..., None])
        ssm = cache["ssm"] * da[..., None, None] + upd
        y = jnp.einsum("bhn,bhnp->bhp", ch.astype(jnp.float32), ssm)
        y = y + xh.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, :, None]
        y = y[:, None]  # (B, 1, H, P)
        new_cache = {"conv": new_conv, "ssm": ssm}

    yf = y.reshape(b, s, di)
    yf = layers.rms_norm(yf.astype(x.dtype), p["out_norm"], cfg.norm_eps)
    yf = yf * jax.nn.silu(z)
    out = layers.dense(yf, p["out_proj"])
    return x + out, new_cache


def init_mamba_cache(cfg, batch: int, dtype):
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.mamba_conv_dim), dtype),
        "ssm": jnp.zeros((batch, cfg.mamba_heads, cfg.d_state, cfg.mamba_headdim), jnp.float32),
    }
