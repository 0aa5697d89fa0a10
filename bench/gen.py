"""The benchmark's inputs, made on the device from ``--seed``.

- ``weights``: a Mamba-2 language model in the layout the program takes
  (``embed``, ``final_norm``, ``blocks/l00/mamba/*`` stacked over layers),
  initialised as mamba_ssm does: normal(0.02) projections and embedding,
  the output projection scaled by 1/sqrt(2 L), depthwise conv weights
  uniform in +-1/sqrt(K), A = -[1, 16] log-uniform, dt log-uniform in
  [1e-3, 1e-1] stored through the inverse softplus, unit norms and skips.
- ``token_batch``: per step and client, rows of a stride walk over the
  vocabulary (stride 1..16, one walk per row) with 15% of the tokens
  replaced by uniform noise, so the loss has something to learn. The walk
  copies ``repro.data.SyntheticLM``'s token law; every (step, client, row)
  draws its own key, so no two rows repeat.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def model_of(config: dict) -> dict:
    """The flat sizes the benchmark's code reads, from a configuration file
    laid out as the source's config.json (Mamba2 sizes under ``ssm_cfg``)."""
    ssm = config["ssm_cfg"]
    out = {k: config[k] for k in ("arch", "d_model", "n_layer", "vocab_size",
                                  "pad_vocab_size_multiple", "norm_epsilon", "dtype", "remat")}
    out.update({k: ssm[k] for k in ("d_state", "d_conv", "expand", "headdim", "ngroups",
                                    "chunk_size")})
    return out


def vocab_padded(model: dict) -> int:
    m = model["pad_vocab_size_multiple"]
    return -(-model["vocab_size"] // m) * m


def weight_shapes(model: dict) -> dict:
    d, layers = model["d_model"], model["n_layer"]
    di, n, k = model["expand"] * d, model["d_state"], model["d_conv"]
    heads = di // model["headdim"]
    conv = di + 2 * model["ngroups"] * n
    mamba = {
        "norm": (layers, d),
        "in_proj": (layers, d, 2 * di + 2 * model["ngroups"] * n + heads),
        "conv_w": (layers, conv, k),
        "conv_b": (layers, conv),
        "dt_bias": (layers, heads),
        "a_log": (layers, heads),
        "d_skip": (layers, heads),
        "out_norm": (layers, di),
        "out_proj": (layers, di, d),
    }
    return {"embed": (vocab_padded(model), d), "final_norm": (d,),
            "blocks": {"l00": {"mamba": mamba}}, "prologue": (), "epilogue": ()}


def weights(model: dict, key):
    """float32 weights from ``key`` (jit this; all leaves in one program)."""
    shapes = weight_shapes(model)
    m = shapes["blocks"]["l00"]["mamba"]
    ks = iter(jax.random.split(key, 8))
    normal = lambda shape, std: std * jax.random.normal(next(ks), shape, jnp.float32)
    u = lambda shape, lo, hi: jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)
    dt = jnp.exp(u(m["dt_bias"], math.log(1e-3), math.log(1e-1)))
    bound = 1.0 / math.sqrt(model["d_conv"])
    mamba = {
        "norm": jnp.ones(m["norm"], jnp.float32),
        "in_proj": normal(m["in_proj"], 0.02),
        "conv_w": u(m["conv_w"], -bound, bound),
        "conv_b": jnp.zeros(m["conv_b"], jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        "a_log": jnp.log(u(m["a_log"], 1.0, 16.0)),
        "d_skip": jnp.ones(m["d_skip"], jnp.float32),
        "out_norm": jnp.ones(m["out_norm"], jnp.float32),
        "out_proj": normal(m["out_proj"], 0.02 / math.sqrt(2 * model["n_layer"])),
    }
    return {"embed": normal(shapes["embed"], 0.02),
            "final_norm": jnp.ones(shapes["final_norm"], jnp.float32),
            "blocks": {"l00": {"mamba": mamba}}, "prologue": (), "epilogue": ()}


def token_batch(key, step, clients: int, batch: int, seq: int, vocab: int):
    """{"inputs", "labels"}: (clients, batch, seq) int32 for step ``step``."""
    def one(c):
        ck = jax.random.fold_in(jax.random.fold_in(key, step), c)
        k1, k2, k3, k4 = jax.random.split(ck, 4)
        shape = (batch, seq + 1)
        start = jax.random.randint(k1, (batch, 1), 0, vocab)
        walk = (start + jnp.cumsum(jax.random.randint(k2, shape, 1, 17), axis=-1)) % vocab
        noise = jax.random.randint(k3, shape, 0, vocab)
        return jnp.where(jax.random.bernoulli(k4, 0.15, shape), noise, walk).astype(jnp.int32)

    toks = jax.vmap(one)(jnp.arange(clients))
    return {"inputs": toks[..., :-1], "labels": toks[..., 1:]}
