"""Shared constants and helpers of the seeded randomized property sweeps
(no third-party property-test dep), one family per file:

- ``test_properties_unbiased.py`` — (a) unbiasedness;
- ``test_properties_variance.py`` — (b) Lemma 4.1-style variance ordering;
- ``test_properties_ledger.py`` — (c) ledger honesty;
- ``test_properties_wire.py`` — (d) rho-tracker calibration and (e)
  entropy-coded wire honesty;
- ``test_properties_budgets.py`` — (f) adaptive per-chunk budgets and (g)
  quantizer internals.

Each family is swept over parametrized grids (>= 200 cases in all) with
deterministic per-case seeds, and the decode families run BOTH through the
monolithic decode and the chunk-ownership sharded decode (docs/DESIGN.md
§10) — the ownership path must preserve every invariant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.dist import collectives

D = 64
C = 2
N = 6
K = 8

# (name, sparsifier ctor) — the unbiased family (top_k excluded: biased)
UNBIASED_SPARSIFIERS = [
    ("rand_k", lambda: codec.RandK(k=K, d_block=D)),
    ("rand_k_spatial", lambda: codec.RandKSpatial(k=K, d_block=D,
                                                  transform="avg")),
    ("rand_proj_spatial", lambda: codec.RandProjSpatial(k=K, d_block=D,
                                                        transform="avg")),
    ("wangni", lambda: codec.Wangni(k=K, d_block=D)),
    ("induced", lambda: codec.Induced(k=K, d_block=D)),
    ("identity", lambda: codec.Identity(d_block=D)),
    ("sparse_proj", lambda: codec.SparseProj(k=K, d_block=D, s=8.0,
                                             transform="avg")),
]

QUANTIZERS = [
    ("none", None),
    ("bf16", codec.Bf16Quant),
    ("int8", codec.Int8Quant),
    ("correlated", codec.CorrelatedQuant),
]


def make_pipeline(sp_ctor, q_ctor):
    stages = [sp_ctor()]
    if q_ctor is not None:
        stages.append(q_ctor())
    return codec.Pipeline(stages)


def client_chunks(seed, n=N, c=C, d=D, rho=None):
    """(n, c, d) client chunks; ``rho`` close to 1 => near-identical rows."""
    rng = np.random.default_rng(seed)
    if rho is None:
        xs = rng.standard_normal((n, c, d))
    else:
        base = rng.standard_normal((c, d))
        noise = rng.standard_normal((n, c, d))
        xs = rho * base[None] + np.sqrt(max(0.0, 1 - rho**2)) * noise
    xs = xs / np.linalg.norm(xs, axis=-1, keepdims=True)
    return jnp.asarray(xs, jnp.float32)


def mc_estimates(pipe, xs, plan, trials, seed):
    """(trials, C, d) decodes under independent round keys; the decode runs
    owner-partitioned when ``plan`` is given."""
    n = xs.shape[0]

    @jax.jit
    def one(key):
        payloads, _ = pipe.encode_all(key, xs)
        if plan is None:
            return pipe.decode_payload(key, payloads, n)
        return collectives.sharded_decode(pipe, key, payloads, n, plan)

    keys = jax.random.split(jax.random.key(seed), trials)
    return np.asarray(jax.lax.map(one, keys))


# small d with k close to it, so SparseProj's density correction F =
# 1 + (k-1)/d + 2(nnz-1)/(nnz d) is ~1.5: the pre-fix tracker (which applied
# the orthonormal-row d/k to sparse_proj) would read ~33% low here and fail
# the tolerance below by a wide margin.
RHO_D, RHO_K, RHO_N = 32, 16, 6
