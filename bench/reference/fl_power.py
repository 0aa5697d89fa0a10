"""Federated power iteration (arXiv:2310.18868 §5) with the compressed mean.

Data: ``samples`` rows of dimension d, low rank plus class structure
(Fashion-MNIST-like moments: rank 16 with scales 3 -> 0.3, ten class
shifts, 0.05 noise), drawn from ``numpy.random.default_rng(seed)``, then
dealt to n clients by an iid shuffle of ``default_rng(SeedSequence([seed,
0x11D]))``, each shard trimmed to the smallest. This is the law of the
program's ``fl.tasks.power_iteration`` data, written out again.

Round t of a run seeded s: round key ``fold_in(key(s), t)``; client i sends
``v_i = X_i^T X_i v / |X_i^T X_i v|``; the server decodes the compressed
mean m of the v_i (``exchange.compressed_mean``, one chunk of d) and sets
``v = m / |m|``. v starts at ``1/sqrt(d)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import exchange
from .lowp import F32, rnd

HIGHEST = jax.lax.Precision.HIGHEST


def image_like(samples: int, d: int, seed: int, n_classes: int = 10) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rank = 16
    basis = rng.standard_normal((rank, d)) * (1.0 / np.sqrt(d))
    scale = np.geomspace(3.0, 0.3, rank)[:, None]
    z = rng.standard_normal((samples, rank))
    labels = rng.integers(0, n_classes, samples)
    shift = rng.standard_normal((n_classes, d)) * 0.4 / np.sqrt(d)
    x = z @ (basis * scale) + shift[labels]
    x = x + rng.standard_normal((samples, d)) * 0.05
    return x.astype(np.float32)


def iid_shards(x: np.ndarray, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x11D]))
    parts = np.array_split(rng.permutation(len(x)), n)
    m = min(len(p) for p in parts)
    return np.stack([x[p[:m]] for p in parts])


def run(shards, mix: dict, seed: int, rounds: int, lowp=None, alter=False):
    """-> (final v, decoded mean of the last round), float32 (d,).
    ``alter`` negates the largest coordinate of every decoded mean: the
    fault of an answer altered where it is produced."""
    n, _, d = shards.shape
    db = mix["d_block"]
    c = -(-d // db)
    xs = rnd(jnp.asarray(shards, F32), lowp)
    key = jax.random.key(seed)
    v = rnd(jnp.ones((d,), F32) / jnp.sqrt(d), lowp)
    mean = v
    for t in range(rounds):
        local = rnd(jnp.einsum("nmd,d->nm", xs, v, precision=HIGHEST), lowp)
        vi = rnd(jnp.einsum("nmd,nm->nd", xs, local, precision=HIGHEST), lowp)
        vi = rnd(vi / (jnp.linalg.norm(vi, axis=1, keepdims=True) + 1e-9), lowp)
        chunks = jnp.pad(vi, ((0, 0), (0, c * db - d))).reshape(n, c, db)
        mean = exchange.compressed_mean(mix, jax.random.fold_in(key, t), chunks,
                                        lowp=lowp).reshape(-1)[:d]
        if alter:
            mean = mean.at[jnp.argmax(jnp.abs(mean))].multiply(-1.0)
        v = rnd(mean / (jnp.linalg.norm(mean) + 1e-9), lowp)
    return v, mean
