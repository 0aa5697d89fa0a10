"""Normalisation constant beta_bar calibration.

Unbiasedness of the family estimator x_hat = (beta/n) (T(S))^dagger sum_i
G_i^T G_i x_i requires (paper App. B.1, and our docs/DESIGN.md §3.4)

    E[ (T(S))^dagger G_i^T G_i ] = (1/beta) I   for every client i
    =>  beta = n d / E[ tr( (T(S))^dagger S ) ]
            = n d / E[ sum_{lambda_j > 0} lambda_j / T(lambda_j) ]

where lambda_j are the eigenvalues of S (equivalently of the nk x nk Gram
matrix A A^T). The paper estimates beta by Monte-Carlo over 1000 runs; we do
the same but (a) jit+vmap the simulation, (b) cache an *eigenvalue bank*
(trials, nk) on disk keyed by (n, k, d), so that beta(rho) for ANY rho is a
cheap in-graph reduction over the bank — this is what makes the online
R-estimation mode (r_mode="est") free, since T_rho only reweights the same
cached eigenvalues.

Closed forms used as fast paths / test oracles:
  rho = 0 (T == 1):  tr(S) = nk exactly (SRHT rows are unit norm)  => beta = d/k.
  rho = 1 (T = id):  sum lambda/T(lambda) = rank(S) ~= nk w.h.p.   => beta ~= d/(nk).

For Rand-k-Spatial the law of the hit-count M_j is Binomial and beta has an
exact expression (no MC): beta = 1 / (p * E[1/T(1+B)]), B ~ Bin(n-1, p),
p = k/d. `rand_k_spatial_beta_weights` returns the pmf so the expectation is
an exact in-graph dot product (again differentiable in rho).
"""
from __future__ import annotations

import fcntl
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from scipy.stats import binom

from ..kernels import ops as kops
from . import transforms

_CACHE_DIR = os.environ.get(
    "REPRO_BETA_CACHE", os.path.join(os.path.dirname(__file__), "..", "..", "..", ".cache", "beta")
)


def default_trials(n: int, k: int) -> int:
    nk = n * k
    return int(max(64, min(512, (1 << 18) // max(nk, 1))))


def _bank_path(n: int, k: int, d: int, trials: int, seed: int, projection: str) -> str:
    os.makedirs(_CACHE_DIR, exist_ok=True)
    return os.path.join(
        _CACHE_DIR, f"{projection}_eigs_n{n}_k{k}_d{d}_t{trials}_s{seed}.npz"
    )


def _simulate_bank(
    n: int, k: int, d: int, trials: int, seed: int, projection: str
) -> np.ndarray:
    """Sample eigenvalues of A A^T, A = stack of n random (k x d) maps.

    May be invoked at trace time (beta is a compile-time constant of the
    decode graph), so force eager compile-time evaluation.
    """

    def one(key):
        keys = jax.random.split(key, n)

        def client(ck):
            k1, k2 = jax.random.split(ck)
            if projection == "srht":
                signs = jax.random.rademacher(k1, (d,), jnp.float32)
                rows = jax.random.permutation(k2, d)[:k]
                return kops.srht_rows_matrix(signs, rows, d)
            if projection == "gauss":
                return jax.random.normal(k1, (k, d)) / jnp.sqrt(d)
            if projection.startswith("sparse"):
                # very-sparse maps (SparseProj): nnz signed entries of
                # magnitude 1/sqrt(nnz) per row, columns WITH replacement
                # (scatter-ADD merges within-row duplicates) — the same law
                # as sparse_proj._client_draw, so the bank's eigenvalue
                # distribution matches the decode's S.
                nnz = int(projection[len("sparse"):])
                cols = jax.random.randint(k2, (k, nnz), 0, d)
                signs = jax.random.rademacher(k1, (k, nnz), jnp.float32)
                g = jnp.zeros((k, d), jnp.float32)
                g = g.at[jnp.arange(k)[:, None], cols].add(signs)
                return g * (1.0 / jnp.sqrt(1.0 * nnz))
            raise ValueError(f"no eig bank for projection {projection!r}")

        a = jax.vmap(client)(keys).reshape(n * k, d)
        gram = a @ a.T
        return jnp.linalg.eigvalsh(gram)

    with jax.ensure_compile_time_eval():
        keys = jax.random.split(jax.random.key(seed), trials)
        # batch to bound memory for large (nk, d)
        bs = max(1, min(trials, (1 << 24) // (n * k * d)))
        outs = []
        fn = jax.vmap(one)
        for i in range(0, trials, bs):
            outs.append(np.asarray(fn(keys[i : i + bs])))
    return np.concatenate(outs, axis=0)


@functools.lru_cache(maxsize=64)
def srht_eig_bank(
    n: int, k: int, d: int, trials: int | None = None, seed: int = 0,
    projection: str = "srht",
) -> np.ndarray:
    """(trials, nk) eigenvalue bank for S with n random-map clients; disk-cached."""
    trials = trials or default_trials(n, k)
    path = _bank_path(n, k, d, trials, seed, projection)
    if not os.path.exists(path):
        # one simulation per bank across processes: the others wait on the
        # lock and then load the bank the first one moved into place whole
        with open(path + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if not os.path.exists(path):
                eigs = _simulate_bank(n, k, d, trials, seed, projection)
                tmp = path + ".tmp.npz"
                np.savez_compressed(tmp, eigs=eigs)
                os.replace(tmp, path)
                return eigs
    return np.load(path)["eigs"]


def sparse_eig_bank(
    n: int, k: int, d: int, nnz: int, trials: int | None = None, seed: int = 0
) -> np.ndarray:
    """Eigenvalue bank for SparseProj's S — same machinery as the SRHT bank,
    keyed (and disk-cached) by the per-row density ``nnz`` as well, since the
    spectrum of S depends on how sparse the maps are."""
    if not 1 <= nnz <= d:
        raise ValueError(f"nnz must be in [1, d={d}], got {nnz}")
    return srht_eig_bank(n, k, d, trials, seed, projection=f"sparse{nnz}")


def beta_fn_from_bank(bank: np.ndarray, n: int, d: int, eps: float = 0.0):
    """-> callable rho -> beta (jnp, differentiable; rho may be traced).

    With ``eps > 0`` the constant calibrates the RIDGE-filtered estimator
    x_hat = (beta_eps/n) (T(S) + eps I)^{-1} y used by the fused CG decode
    (docs/DESIGN.md §3.5): the same isotropy argument applies verbatim to
    T_eps(lambda) = T(lambda) + eps, so unbiasedness is exact, not
    approximate. Because T_eps is bounded away from zero the spectral
    floor used at eps == 0 to emulate the pseudo-inverse is dropped —
    near-zero bank eigenvalues self-suppress via lambda / (T(lambda) + eps).
    """
    bank_j = jnp.asarray(bank)

    def beta(rho):
        t = transforms.t_apply(bank_j, rho) + eps
        if eps > 0.0:
            contrib = jnp.maximum(bank_j, 0.0) / t
        else:
            contrib = jnp.where(bank_j > 1e-4, bank_j / t, 0.0)
        c = jnp.mean(jnp.sum(contrib, axis=-1)) / (n * d)
        return 1.0 / c

    return beta


def srht_beta(n: int, k: int, d: int, rho: float, trials: int | None = None, seed: int = 0) -> float:
    """Scalar beta_bar for Rand-Proj-Spatial(SRHT) with T_rho."""
    if rho == 0.0:
        return d / k  # exact: tr(S) = nk
    bank = srht_eig_bank(n, k, d, trials, seed)
    return float(beta_fn_from_bank(bank, n, d)(rho))


# ---------------------------------------------------------------- Rand-k-Spatial


@functools.lru_cache(maxsize=256)
def rand_k_spatial_beta_weights(n: int, k: int, d: int) -> tuple[float, np.ndarray]:
    """(p, pmf of B ~ Bin(n-1, p)) with p = k/d, in float64."""
    p = k / d
    b = np.arange(n)
    return p, binom.pmf(b, n - 1, p)


def rand_k_spatial_beta(n: int, k: int, d: int, rho) -> jnp.ndarray:
    """Exact beta = 1 / (p E[1/T(1+B)]); rho may be traced (in-graph)."""
    p, pmf = rand_k_spatial_beta_weights(n, k, d)
    m = jnp.asarray(1.0 + np.arange(n), jnp.float32)  # 1 + B
    inv_t = 1.0 / transforms.t_apply(m, rho)
    # multiply + row-sum rather than jnp.dot: a batched dot (this is vmapped
    # over per-chunk rho in r_mode="est") may pick a batch-shape-dependent
    # reduction, breaking the ownership slice-parity contract at 1 ulp.
    return 1.0 / (p * jnp.sum(jnp.asarray(pmf, jnp.float32) * inv_t, axis=-1))
