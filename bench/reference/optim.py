"""AdamW as the benchmark's configurations state it: global-norm gradient
clipping, bias-corrected moments, a linear warm-up of the learning rate
over ``warmup_steps`` (step t, counted from 1, uses lr * min(1, (t+1)/W)),
and decoupled weight decay."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(tree)))


def init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"mu": z, "nu": jax.tree.map(jnp.zeros_like, params), "t": jnp.zeros((), jnp.int32)}


def update(opt: dict, grads, state, params):
    """One step; jit with ``functools.partial(update, opt)``."""
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    norm = global_norm(grads)
    if opt["grad_clip"] > 0:
        scale = jnp.minimum(1.0, opt["grad_clip"] / (norm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"], grads)
    lr = opt["lr"] * jnp.minimum(1.0, (tf + 1) / max(opt["warmup_steps"], 1))
    c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf

    def step(p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) + opt["weight_decay"] * p
        return p - lr * u

    return jax.tree.map(step, params, mu, nu), {"mu": mu, "nu": nu, "t": t}
