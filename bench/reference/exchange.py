"""The compressed mean of n clients' vectors, as the estimators define it.

Vectors are cut into chunks of ``d`` coordinates (the flattened pytree,
zero-padded at the end). The round key ``key`` gives each client i the key
``fold_in(key, i)``, and with per-chunk draws each chunk c the key
``fold_in(fold_in(key, i), c)``; nothing random is sent.

- ``rand_k_spatial`` (Jhunjhunwala et al. 2021): client i sends its
  coordinates ``permutation(key_ic, d)[:k]``; the server sums them, counts
  how many clients sent each coordinate (m_j) and returns
  ``beta/n * sum / T(m_j)`` with ``T(m) = 1 + rho (m - 1)`` and the exact
  binomial ``beta = 1 / (p E[1/T(1+B)])``, ``B ~ Bin(n-1, p)``, p = k/d.
- ``rand_proj_spatial`` (arXiv:2310.18868, Eq. 5, with the SRHT maps of
  Eq. 6): ``G_i = d^-1/2 E_i H D_i``, signs ``rademacher(k1)`` and rows the
  k largest of ``bits(k2)`` for ``k1, k2 = split(key_ic)``; client i sends
  ``G_i x_i``; the server solves ``((1 - rho + eps) I + rho S) x = y`` with
  ``S = sum_i G_i^T G_i`` and ``y = sum_i G_i^T z_i`` and returns
  ``beta_eps/n * x``, ``beta_eps = n d / E[sum_j l_j / (T(l_j) + eps)]``
  over the eigenvalues l_j of ``A A^T``, A the stacked maps, estimated
  here by Monte Carlo over fresh draws.

``rho`` is the transform's weight: 0 (one), 1 (max), (n/2)/(n-1) (avg).
``lowp`` rounds every array of the exchange to a lower precision (the
control). Chunks are processed in blocks, so that full-size gradients fit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from .lowp import F32, rnd

HIGHEST = jax.lax.Precision.HIGHEST
CG_ITERS = 40          # the resolvent is condition <= 1 + rho n / (1 - rho + eps)
BETA_MC_KEY = 0x5EED0B   # Monte Carlo draws of the maps for beta_eps


def rho_for(transform: str, n: int) -> float:
    return {"one": 0.0, "max": 1.0, "avg": (n / 2.0) / (n - 1.0)}[transform]


def to_chunks(tree, d: int):
    flat = ravel_pytree(tree)[0].astype(F32)
    c = -(-flat.shape[0] // d)
    return jnp.pad(flat, (0, c * d - flat.shape[0])).reshape(c, d)


def to_chunks_count(size: int, d: int) -> int:
    return -(-size // d)


def from_chunks(chunks, like):
    flat, unravel = ravel_pytree(like)
    return unravel(chunks.reshape(-1)[: flat.shape[0]])


def _hadamard(d: int) -> np.ndarray:
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < d:
        h = np.block([[h, h], [h, -h]])
    return h


def _fwht(x, ha, hb, lowp):
    """Sylvester H_d x along the last axis, H_d = H_a (x) H_b."""
    a, b = ha.shape[0], hb.shape[0]
    xr = rnd(x.reshape(x.shape[:-1] + (a, b)), lowp)
    y = jnp.einsum("ij,...jk,lk->...il", ha, xr, hb, precision=HIGHEST)
    return rnd(y.reshape(x.shape), lowp)


def _factors(d: int):
    m = int(math.log2(d))
    if 1 << m != d:
        raise ValueError(f"d must be a power of two, got {d}")
    return jnp.asarray(_hadamard(1 << (m // 2))), jnp.asarray(_hadamard(1 << (m - m // 2)))


def _client_keys(key, n, c0, c, per_chunk):
    """(n, c|1) keys of clients 0..n-1 over chunks c0..c0+c-1."""
    def one(i):
        ck = jax.random.fold_in(key, i)
        if not per_chunk:
            return ck[None]
        return jax.vmap(jax.random.fold_in, (None, 0))(ck, c0 + jnp.arange(c))
    return jax.vmap(one)(jnp.arange(n))


def srht_draw(kk, d, k):
    k1, k2 = jax.random.split(kk)
    signs = jax.random.rademacher(k1, (d,), F32)
    rows = jax.lax.top_k(jax.random.bits(k2, (d,), jnp.uint32), k)[1]
    return signs, rows


def randk_rows(kk, d, k):
    return jax.random.permutation(kk, d)[:k]


def _rps_block(key, xs, c0, k, rho, eps, per_chunk, lowp):
    """Decode of one block of chunks: xs (n, c, d) -> unscaled solve x (c, d)."""
    n, c, d = xs.shape
    ha, hb = _factors(d)
    keys = _client_keys(key, n, c0, c, per_chunk)
    signs, rows = jax.vmap(jax.vmap(lambda kk: srht_draw(kk, d, k)))(keys)
    rows = jnp.broadcast_to(rows, (n, c, k))
    mask = jax.vmap(jax.vmap(lambda r: jnp.zeros((d,), F32).at[r].set(1.0)))(rows)
    inv = 1.0 / math.sqrt(d)

    def g(v):                       # (c, d) -> per client G_i^T G_i v summed
        t = _fwht(signs * v[None], ha, hb, lowp) * inv
        return jnp.sum(signs * _fwht(mask * t, ha, hb, lowp) * inv, axis=0)

    t = _fwht(signs * rnd(xs, lowp), ha, hb, lowp) * inv
    z = rnd(jnp.take_along_axis(t, rows, axis=-1), lowp)          # (n, c, k)
    scat = jax.vmap(jax.vmap(lambda zz, r: jnp.zeros((d,), F32).at[r].set(zz)))(z, rows)
    y = jnp.sum(signs * _fwht(scat, ha, hb, lowp) * inv, axis=0)  # (c, d)

    def apply_m(v):
        return rnd((1.0 - rho + eps) * v + rho * g(v), lowp)

    def cg(_, carry):
        x, r, p, rs = carry
        ap = apply_m(p)
        pap = jnp.sum(p * ap, axis=-1, keepdims=True)
        alpha = jnp.where(pap > 0, rs / jnp.where(pap > 0, pap, 1.0), 0.0)
        x, r = rnd(x + alpha * p, lowp), rnd(r - alpha * ap, lowp)
        rs2 = jnp.sum(r * r, axis=-1, keepdims=True)
        p = rnd(r + jnp.where(rs > 0, rs2 / jnp.where(rs > 0, rs, 1.0), 0.0) * p, lowp)
        return x, r, p, rs2

    rs0 = jnp.sum(y * y, axis=-1, keepdims=True)
    x, *_ = jax.lax.fori_loop(0, CG_ITERS, cg, (jnp.zeros_like(y), y, y, rs0))
    return x


def _rks_block(key, xs, c0, k, rho, per_chunk, lowp):
    n, c, d = xs.shape
    keys = _client_keys(key, n, c0, c, per_chunk)
    rows = jax.vmap(jax.vmap(lambda kk: randk_rows(kk, d, k)))(keys)
    rows = jnp.broadcast_to(rows, (n, c, k))
    vals = rnd(jnp.take_along_axis(rnd(xs, lowp), rows, axis=-1), lowp)
    put = jax.vmap(jax.vmap(lambda v, r: jnp.zeros((d,), F32).at[r].set(v)))
    s = jnp.sum(put(vals, rows), axis=0)
    m = jnp.sum(put(jnp.ones_like(vals), rows), axis=0)
    t = 1.0 + rho * (m - 1.0)
    return jnp.where(m > 0, s / jnp.where(m > 0, t, 1.0), 0.0)


def rand_k_spatial_beta(n: int, k: int, d: int, rho: float) -> float:
    p = k / d
    e = sum(math.comb(n - 1, b) * p ** b * (1 - p) ** (n - 1 - b) / (1.0 + rho * b)
            for b in range(n))
    return 1.0 / (p * e)


@functools.lru_cache(maxsize=None)
def srht_beta(n: int, k: int, d: int, rho: float, eps: float) -> float:
    """n d / E[sum_j l_j / (1 - rho + eps + rho l_j)] by Monte Carlo, with
    sum_j l_j / (a + rho l_j) = (nk - a tr((a I + rho A A^T)^-1)) / rho.
    Trials: 2^18 / (nk), within [64, 512]; the statistic's spread over draws
    falls with nk, so its relative error stays near 1e-4."""
    trials = max(64, min(512, (1 << 18) // (n * k)))
    h = jnp.asarray(_hadamard(d))
    a = 1.0 - rho + eps

    @jax.jit
    def one(kk):
        keys = jax.random.split(kk, n)
        signs, rows = jax.vmap(lambda q: srht_draw(q, d, k))(keys)
        g = h[rows] * signs[:, None, :] / math.sqrt(d)                # (n, k, d)
        amat = g.reshape(n * k, d)
        gram = jnp.matmul(amat, amat.T, precision=HIGHEST)
        if rho == 0.0:
            return jnp.trace(gram) / a
        inv = jnp.linalg.inv(a * jnp.eye(n * k) + rho * gram)
        return (n * k - a * jnp.trace(inv)) / rho

    keys = jax.random.split(jax.random.key(BETA_MC_KEY), trials)
    total = float(jnp.mean(jax.lax.map(one, keys, batch_size=min(trials, 64))))
    return n * d / total


@functools.lru_cache(maxsize=None)
def _decoder(estimator, k, rho, eps, per_chunk, lowp):
    if estimator == "rand_proj_spatial":
        return jax.jit(lambda kk, x, c0: _rps_block(kk, x, c0, k, rho, eps, per_chunk, lowp))
    if estimator == "rand_k_spatial":
        return jax.jit(lambda kk, x, c0: _rks_block(kk, x, c0, k, rho, per_chunk, lowp))
    raise ValueError(f"no reference for estimator {estimator!r}")


def compressed_mean(mix: dict, key, xs, block: int = 16384, lowp=None):
    """(n, C, d) client chunks -> (C, d) decoded mean, per ``mix``."""
    n, c, d = xs.shape
    k, per_chunk = mix["k"], not mix["shared_randomness"]
    rho = rho_for(mix["transform"], n)
    eps = mix.get("ridge", 0.0)
    if mix["estimator"] == "rand_proj_spatial":
        beta = srht_beta(n, k, d, rho, eps)
    else:
        beta = rand_k_spatial_beta(n, k, d, rho)
    fn = _decoder(mix["estimator"], k, rho, eps, per_chunk, lowp)
    block = min(block, c)
    pad = -c % block
    xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
    out = [fn(key, xs[:, i:i + block], i) for i in range(0, c + pad, block)]
    return rnd((beta / n) * jnp.concatenate(out, axis=0)[:c], lowp)
