"""Operations and bytes computed from shapes.

- ``mamba2_train_flops_per_token``: what one token of a training step
  needs. Six FLOPs per parameter and token (forward 2, backward 4; the
  tied embedding counts once, for the output head) plus the SSD scan,
  which no parameter count holds: per layer and token, with chunks of Q
  positions, state N, H heads of P channels and g B/C groups, the forward
  pass takes ``2 [g (Q+1)/2 N + H (Q+1)/2 P + 2 H N P]`` FLOPs (the causal
  half of the in-chunk C.B^T scores and of their product with x, then the
  chunk state and its read-out), times three for forward and backward.
  The depthwise conv, norms and gates (under 1% together) are left out,
  and so are recomputed (rematerialised) operations.
- ``srht_gram_apply``: one call of the fused ``S v = sum_i G_i^T G_i v``
  over C chunks of d for n clients whose sign and mask rows come per chunk
  (``c_draw = C``) or shared (``c_draw = 1``): two length-d fast
  Walsh-Hadamard transforms (d log2 d additions each) plus four
  elementwise passes per client and chunk, and the float32 arrays the call
  takes and gives: v and S v (C x d), signs and mask (n x c_draw x d).
"""
from __future__ import annotations

import math

from . import gen


def n_params(model: dict) -> int:
    def size(shape):
        return math.prod(shape)

    shapes = gen.weight_shapes(model)
    leaves = [shapes["embed"], shapes["final_norm"],
              *shapes["blocks"]["l00"]["mamba"].values()]
    return sum(size(s) for s in leaves)


def ssd_flops_per_token(model: dict) -> float:
    q, n, g = model["chunk_size"], model["d_state"], model["ngroups"]
    di = model["expand"] * model["d_model"]
    p = model["headdim"]
    h = di // p
    fwd = 2 * (g * (q + 1) / 2 * n + h * (q + 1) / 2 * p + 2 * h * n * p)
    return 3 * fwd * model["n_layer"]


def mamba2_train_flops_per_token(model: dict) -> float:
    return 6.0 * n_params(model) + ssd_flops_per_token(model)


def srht_gram_apply(n: int, c: int, c_draw: int, d: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call."""
    flops = n * c * (2 * d * math.log2(d) + 4 * d)
    nbytes = 4 * (2 * c * d + 2 * n * c_draw * d)
    return flops, nbytes
