"""Plain Mamba-2 language model (arXiv:2405.21060), float32, no kernels.

Parameters use the layout the benchmark builds for the program: a dict with
``embed`` (V_pad, D) tied to the output head, ``final_norm`` (D,) and
``blocks/l00/mamba/<name>`` stacked over layers. Per layer::

    h = rmsnorm(x) @ in_proj -> z, xBC, dt       dt = softplus(dt + dt_bias)
    xBC = silu(causal depthwise conv(xBC) + conv_b) -> x, B, C
    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t
    x <- x + (rmsnorm(y) * silu(z)) @ out_proj,   A = -exp(a_log)

The scan is written in its quadratic ("dual") form over the whole sequence,
not chunked. Departure from the published block, kept because the program
does the same: the gate multiplies after the output norm
(``rmsnorm(y) * silu(z)``; the published Mamba-2 gates before it). One
B/C group (``ngroups = 1``), as in mamba2-130m.

``mm`` names the precision matmul operands are rounded to (``None``:
float32 at ``HIGHEST``); the control runs the same model with fp8 operands.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .lowp import F32, rnd

HIGHEST = jax.lax.Precision.HIGHEST


def _dense(x, w, mm):
    return jnp.matmul(rnd(x, mm), rnd(w, mm), precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer(x, p, dims, mm):
    b, s, _ = x.shape
    di, n, heads, hd, eps = (dims["d_inner"], dims["d_state"], dims["heads"],
                             dims["headdim"], dims["eps"])
    zxbcdt = _dense(_rmsnorm(x, p["norm"], eps), p["in_proj"], mm)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # (b, s, H)
    kc = p["conv_w"].shape[-1]
    pad = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + s, :] * p["conv_w"][:, j] for j in range(kc))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(b, s, heads, hd)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    cs = jnp.cumsum(dt * -jnp.exp(p["a_log"]), axis=1)             # (b, s, H)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, cs[:, :, None, :] - cs[:, None, :, :], -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cm, bm, precision=HIGHEST)
    w = cb[..., None] * decay * dt[:, None, :, :]                  # (b, t, s, H)
    y = jnp.einsum("btsh,bshp->bthp", w, xs, precision=HIGHEST)
    y = y + xs * p["d_skip"][None, None, :, None]
    y = _rmsnorm(y.reshape(b, s, di), p["out_norm"], eps) * jax.nn.silu(z)
    return x + _dense(y, p["out_proj"], mm)


def dims_of(model: dict) -> dict:
    d = model["d_model"]
    di = model["expand"] * d
    return {"d_inner": di, "d_state": model["d_state"],
            "heads": di // model["headdim"], "headdim": model["headdim"],
            "eps": model["norm_epsilon"]}


def loss(params, batch, model: dict, mm=None):
    """Mean next-token cross-entropy over the batch (all labels valid)."""
    if model.get("ngroups", 1) != 1:
        raise ValueError("the reference covers one B/C group")
    dims = dims_of(model)
    x = jnp.take(params["embed"], batch["inputs"], axis=0).astype(F32)

    def body(h, p):
        return _layer(h, p, dims, mm), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"]["l00"]["mamba"])
    x = _rmsnorm(x, params["final_norm"], dims["eps"])
    logits = _dense(x, params["embed"].T, mm)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_and_grad(model: dict, mm=None):
    """jitted (params, one client's batch) -> (loss, grads)."""
    return jax.jit(jax.value_and_grad(lambda p, b: loss(p, b, model, mm)))
