"""Rand-Proj-Spatial family estimator (paper Eq. 5) — the core contribution.

Encode (client i):   xh_i = G_i x_i,  G_i = (1/sqrt(d)) E_i H D_i   (SRHT, Eq. 6)
Decode (server):     x_hat = (beta/n) (T(S))^dagger sum_i G_i^T G_i x_i,
                     S = sum_i G_i^T G_i,  T applied to S's eigenvalues.

Three decode paths (tests assert they agree to float tolerance):

- ``direct``  — the paper-literal algorithm: materialise S (d x d), eigh,
  apply T to the spectrum. O(d^2 nk). Kept as the faithful oracle.
- ``gram``    — our TPU adaptation (docs/DESIGN.md §3.3): with A = [G_1; ...; G_n]
  (nk x d) and z = concat of received payloads, S = A^T A and

      x_hat = (beta/n) * A^T U diag(1_{l>0} / T(l)) U^T z,
      A A^T = U diag(l) U^T   (nk x nk Gram eigendecomposition)

  which is EXACT (y = A^T z lies in range(S)) and costs O((nk)^2 d) MXU
  matmuls + one small eigh — removing the paper's Limitation #1.
- ``fused``   — the kernel fast path (docs/DESIGN.md §3.5, docs/KERNELS.md),
  default via ``decode_method="auto"`` for srht/subsample projections. The
  family transform is AFFINE, T(lambda) = 1 - rho + rho*lambda, so applying
  (T(S))^dagger to y = sum_i G_i^T z_i (which lies in range(S)) is a linear
  resolvent solve, not a spectral one:

      ((1 - rho + eps) I + rho S) x = y,      x_hat = (beta_eps / n) x

  solved matrix-free by conjugate gradients, where every S v is ONE fused
  Pallas launch (two FWHTs with a coordinate mask between them, batched over
  clients x chunks — kernels/srht_fused.py). No A materialisation, no eigh.
  The ridge eps keeps the solve well-posed at rho = 1; unbiasedness stays
  EXACT because beta is recalibrated against T_eps = T + eps (see
  beta.beta_fn_from_bank). With projection="subsample" S is diagonal (the
  hit-counts), the solve is closed-form with eps = 0, and the fused path is
  Rand-k-Spatial (Lemma 4.1) without any linear algebra.

``shared_randomness=True`` uses one {G_i} draw for all chunks of a round, so
a single Gram eigendecomposition serves every chunk and the per-chunk work
is two matmuls. ``False`` is the paper-faithful independent-per-chunk mode
(vmapped) used by the fidelity benchmarks.

Projections: "srht" (the paper's choice), "subsample" (recovers
Rand-k-Spatial exactly — Lemma 4.1), "gauss" (comparison baseline).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...kernels import ops as kops
from ...kernels import ref as kref
from ...obs import record_cg_iters, record_decode_route
from .. import beta as beta_lib
from .. import transforms
from . import base

_EPS = 1e-4
_CG_TOL = 1e-4  # relative residual target of the fused resolvent solve


def _client_draw(spec, ckey):
    """One (signs, rows) draw for a single client / single chunk."""
    d, k = spec.d_block, spec.k
    k1, k2 = jax.random.split(ckey)
    proj = getattr(spec, "projection", None) or "srht"
    if proj == "srht":
        signs = jax.random.rademacher(k1, (d,), jnp.float32)
        # Uniform k-subset via top_k over random bits: same law as
        # permutation(d)[:k] (rows stay distinct, as G_i G_i^T = I_k
        # requires) but ~6x cheaper — permutation dominates the whole
        # fused-decode walltime at fig5 scale otherwise.
        rows = jax.lax.top_k(jax.random.bits(k2, (d,), jnp.uint32), k)[1]
        return {"signs": signs, "rows": rows}
    if proj == "subsample":
        # derive rows exactly as rand_k._indices does (from the unsplit client
        # key) so Lemma 4.1 holds bit-for-bit against Rand-k-Spatial.
        rows = jax.random.permutation(ckey, d)[:k]
        return {"rows": rows}
    if proj == "gauss":
        g = jax.random.normal(k1, (k, d)) / jnp.sqrt(d)
        return {"g": g}
    raise ValueError(f"unknown projection {proj!r}")


def _apply_g(spec, draw, x_cd):
    """G x for a chunk batch: (C, d) -> (C, k)."""
    if "signs" in draw:
        return kops.srht_encode(x_cd, draw["signs"], draw["rows"], use_pallas=spec.use_pallas)
    if "g" in draw:
        return x_cd @ draw["g"].T
    return jnp.take(x_cd, draw["rows"], axis=-1)


def _g_matrix(spec, draw):
    """Materialise G (k, d) for the Gram/direct decode."""
    d = spec.d_block
    if "signs" in draw:
        return kops.srht_rows_matrix(draw["signs"], draw["rows"], d)
    if "g" in draw:
        return draw["g"]
    return jax.nn.one_hot(draw["rows"], d, dtype=jnp.float32)


def encode(spec, key, client_id, x_cd):
    ckey = base.client_key(key, client_id)
    c = x_cd.shape[0]
    if spec.shared_randomness:
        draw = _client_draw(spec, ckey)
        vals = _apply_g(spec, draw, x_cd)
    else:
        keys = jax.vmap(base.chunk_key, in_axes=(None, 0))(ckey, jnp.arange(c))
        draws = jax.vmap(lambda kk: _client_draw(spec, kk))(keys)
        if "signs" in draws:
            # fused batched encode: per-chunk sign flip + FWHT in one pass
            # (kernels/srht_fused.py), then the row-wise select.
            vals = kops.srht_encode_batch(
                x_cd, draws["signs"], draws["rows"], use_pallas=spec.use_pallas
            )
        elif "g" in draws:
            vals = jnp.einsum("ckd,cd->ck", draws["g"], x_cd)
        else:
            vals = jnp.take_along_axis(x_cd, draws["rows"], axis=-1)
    out = {"vals": vals}
    if spec.r_mode == "est":
        out["norm_sq"] = jnp.sum(x_cd.astype(jnp.float32) ** 2, axis=-1)
    return out


def _stack_a(spec, key, n, chunk_id=None, client_ids=None):
    """A = [G_1; ...; G_n] (nk, d) re-derived from the round key.

    ``client_ids`` selects which clients' maps to stack (participants)."""

    def one(i):
        ckey = base.client_key(key, i)
        if chunk_id is not None:
            ckey = base.chunk_key(ckey, chunk_id)
        return _g_matrix(spec, _client_draw(spec, ckey))

    ids = jnp.arange(n) if client_ids is None else jnp.asarray(client_ids)
    mats = jax.vmap(one)(ids)  # (n, k, d)
    return mats.reshape(n * spec.k, spec.d_block)


def _rho_hat(spec, n, z, gram, norm_sq):
    """Per-chunk online R-hat (docs/DESIGN.md §5). z: (C, n, k); gram: (nk, nk)."""
    d, k = spec.d_block, spec.k
    scale = d / k
    zf = z.reshape(z.shape[0], n * k)
    total_sq = scale**2 * jnp.einsum("cp,pq,cq->c", zf, gram, zf)
    g4 = gram.reshape(n, k, n, k)
    diag_blocks = g4[jnp.arange(n), :, jnp.arange(n), :]  # (n, k, k)
    per_client_sq = scale**2 * jnp.einsum("cnk,nkl,cnl->c", z, diag_blocks, z)
    r_hat = (total_sq - per_client_sq) / (jnp.sum(norm_sq, axis=0) + 1e-12)
    return transforms.clip_rho(r_hat / (n - 1.0), n)  # (C,)


def _spectral_weights(spec, n, lam, rho):
    """1_{l>0} / T(l) per eigenvalue; rho scalar or per-chunk (C,)."""
    mask = lam > _EPS * jnp.max(lam)
    if jnp.ndim(rho) == 0:
        t = transforms.t_apply(lam, rho)
        return jnp.where(mask, 1.0 / t, 0.0)
    t = transforms.t_apply(lam[None, :], rho[:, None])
    return jnp.where(mask[None, :], 1.0 / t, 0.0)  # (C, nk)


def _beta(spec, n, rho, eps: float = 0.0):
    if spec.projection == "subsample":
        # eigenvalues of S are the binomial hit-counts M_j: beta is exact
        # (Lemma 4.1: the estimator IS Rand-k-Spatial). The fused decode
        # solves the diagonal system exactly, so no ridge is involved.
        def fn(r):
            return beta_lib.rand_k_spatial_beta(n, spec.k, spec.d_block, r)
    else:
        bank = beta_lib.srht_eig_bank(
            n, spec.k, spec.d_block, spec.beta_trials, projection=spec.projection
        )
        fn = beta_lib.beta_fn_from_bank(bank, n, spec.d_block, eps=eps)
    if jnp.ndim(rho) == 0:
        return fn(rho)
    return jax.vmap(fn)(rho)


def _decode_one_gram(spec, n, a, z, norm_sq):
    """Gram-trick decode. a: (nk, d); z: (C, n, k) -> (C, d)."""
    gram = a @ a.T  # (nk, nk) — MXU
    lam, u = jnp.linalg.eigh(gram)
    if spec.r_mode == "est":
        rho = _rho_hat(spec, n, z, gram, norm_sq)
    else:
        rho = jnp.asarray(transforms.rho_for(spec.transform, n, spec.r_value))
    w = _spectral_weights(spec, n, lam, rho)  # (nk,) or (C, nk)
    b = _beta(spec, n, rho)  # scalar or (C,)
    zf = z.reshape(z.shape[0], n * spec.k)
    proj = (zf @ u) * (w if w.ndim == 2 else w[None, :])  # (C, nk)
    y = proj @ u.T  # (C, nk)
    xh = y @ a  # (C, d) — MXU
    scale = (b / n) if jnp.ndim(b) == 0 else (b / n)[:, None]
    return scale * xh


def _decode_one_direct(spec, n, a, z, norm_sq):
    """Paper-literal decode: eigh of S = A^T A (d x d). Oracle path."""
    s = a.T @ a
    lam, v = jnp.linalg.eigh(s)  # (d,), (d, d)
    gram = a @ a.T
    if spec.r_mode == "est":
        rho = _rho_hat(spec, n, z, gram, norm_sq)
    else:
        rho = jnp.asarray(transforms.rho_for(spec.transform, n, spec.r_value))
    mask = lam > _EPS * jnp.max(lam)
    if jnp.ndim(rho) == 0:
        w = jnp.where(mask, 1.0 / transforms.t_apply(lam, rho), 0.0)[None, :]
    else:
        w = jnp.where(
            mask[None, :], 1.0 / transforms.t_apply(lam[None, :], rho[:, None]), 0.0
        )
    b = _beta(spec, n, rho)
    zf = z.reshape(z.shape[0], n * spec.k)
    y = zf @ a  # (C, d): y_c = A^T z_c
    xh = ((y @ v) * w) @ v.T
    scale = (b / n) if jnp.ndim(b) == 0 else (b / n)[:, None]
    return scale * xh


def _fused_draws(spec, key, n, c, client_ids, chunk_offset):
    """All (client x chunk) draws, stacked for the batched kernels.

    Returns leaves of shape (n, 1, ...) in shared_randomness mode (one draw
    per client, broadcast over chunks) and (n, C, ...) otherwise. Chunk draws
    are keyed by GLOBAL chunk position (chunk_offset + local index), so an
    owner's slice decode re-derives the full decode's maps.
    """
    ids = jnp.arange(n) if client_ids is None else jnp.asarray(client_ids)
    if spec.shared_randomness:
        draws = jax.vmap(lambda i: _client_draw(spec, base.client_key(key, i)))(ids)
        return jax.tree.map(lambda v: v[:, None], draws)
    chunk_ids = chunk_offset + jnp.arange(c)

    def one(i):
        ckey = base.client_key(key, i)
        return jax.vmap(lambda cid: _client_draw(spec, base.chunk_key(ckey, cid)))(
            chunk_ids
        )

    return jax.vmap(one)(ids)


def _cg_resolvent_solve(y, rho, eps, apply_s, iters):
    """Batched CG for ((1 - rho + eps) I + rho S) x = y, one system per chunk.

    All reductions are per-chunk (row-independent), and converged chunks are
    FROZEN via jnp.where — so decoding an owner's chunk slice is bitwise
    identical to slicing the monolithic decode, regardless of how many extra
    iterations the slowest chunk in the batch needs (the ownership-sharding
    contract, tests/test_ownership.py).

    y: (C, d); rho: scalar or (C,). Zero-payload chunks (y = 0, e.g. the
    padding added by collectives.sharded_decode) converge at iteration 0 and
    return exactly 0 — the alpha denominator is guarded so they cannot NaN.
    """
    c0 = 1.0 - rho + eps
    c1 = rho

    def col(v):
        return v if jnp.ndim(v) == 0 else v[:, None]

    def apply_m(v):
        return col(c0) * v + col(c1) * apply_s(v)

    ys = jnp.sum(y * y, axis=-1, keepdims=True)  # (C, 1)
    tol2 = (_CG_TOL * _CG_TOL) * ys
    x = jnp.zeros_like(y)
    done = ys <= tol2  # catches y == 0 exactly
    carry = (jnp.int32(0), x, y, y, ys, done)

    def cond(carry):
        it, _, _, _, _, done = carry
        return (it < iters) & ~jnp.all(done)

    def body(carry):
        it, x, r, p, rs, done = carry
        ap = apply_m(p)
        pap = jnp.sum(p * ap, axis=-1, keepdims=True)
        alpha = jnp.where(done, 0.0, rs / jnp.where(pap > 0, pap, 1.0))
        x2 = jnp.where(done, x, x + alpha * p)
        r2 = jnp.where(done, r, r - alpha * ap)
        rs2 = jnp.where(done, rs, jnp.sum(r2 * r2, axis=-1, keepdims=True))
        done2 = done | (rs2 <= tol2)
        bet = jnp.where(done2, 0.0, rs2 / jnp.where(rs > 0, rs, 1.0))
        p2 = jnp.where(done2, p, r2 + bet * p)
        return it + 1, x2, r2, p2, rs2, done2

    it, x, _, _, _, _ = jax.lax.while_loop(cond, body, carry)
    return x, it


def _decode_fused(spec, key, payloads, n, client_ids, chunk_offset):
    """Kernel fast-path decode: batched over (clients x chunks), no eigh.

    y = sum_i G_i^T z_i is one fused scatter-add launch; (T(S))^dagger y is a
    matrix-free resolvent solve (CG whose inner apply is one fused Gram
    launch), or a closed-form diagonal solve for projection="subsample".
    """
    d, k = spec.d_block, spec.k
    vals = payloads["vals"].astype(jnp.float32)  # (n, C, k)
    norm_sq = payloads.get("norm_sq")
    c = vals.shape[1]
    draws = _fused_draws(spec, key, n, c, client_ids, chunk_offset)
    rows = draws["rows"]  # (n, Cs, k), Cs in {1, C}
    signs = draws.get("signs")

    if signs is not None:
        y = kops.srht_decode_sum(vals, signs, rows, d, use_pallas=spec.use_pallas)
    else:
        y = jnp.sum(kref.srht_scatter_ref(vals, rows, d), axis=0)  # (C, d)

    if spec.r_mode == "est":
        # matrix-free R-hat (docs/DESIGN.md §5): z^T A A^T z = ||A^T z||^2 =
        # ||y||^2 and z_i^T G_i G_i^T z_i = ||z_i||^2 (G_i G_i^T = I_k exactly
        # for srht and subsample maps), so no Gram matrix is needed and the
        # statistic is per-chunk — it shards untouched across owners.
        sc = (d / k) ** 2
        tot = sc * jnp.sum(y * y, axis=-1)  # (C,)
        per = sc * jnp.sum(vals * vals, axis=(0, 2))  # (C,)
        r_hat = (tot - per) / (jnp.sum(norm_sq, axis=0) + 1e-12)
        rho = transforms.clip_rho(r_hat / (n - 1.0), n)  # (C,)
    else:
        rho = jnp.asarray(transforms.rho_for(spec.transform, n, spec.r_value))

    mask = kops.expand_coords(1.0, rows, d, use_pallas=spec.use_pallas)  # (n, Cs, d)

    if spec.projection == "subsample":
        # S = diag(hit counts): (T(S))^dagger is a closed-form elementwise
        # divide — the fused path IS Rand-k-Spatial (Lemma 4.1), eps = 0.
        hits = jnp.sum(mask, axis=0)  # (Cs, d)
        t = transforms.t_apply(hits, rho if jnp.ndim(rho) == 0 else rho[:, None])
        # explicit reciprocal-then-multiply: keeps the op sequence identical
        # across batch shapes (XLA may otherwise hoist broadcast divides),
        # which the ownership slice-parity contract relies on.
        xh = y * jnp.where(hits > 0, 1.0 / t, 0.0)
        b = _beta(spec, n, rho)
    else:
        eps = getattr(spec, "ridge", 1e-2)
        iters = getattr(spec, "cg_iters", 64)

        def apply_s(v):
            return kops.srht_gram_apply(v, signs, mask, use_pallas=spec.use_pallas)

        xh, cg_it = _cg_resolvent_solve(y, rho, eps, apply_s, iters)
        record_cg_iters(cg_it)  # eager runs sample; under jit it's a tracer -> dropped
        b = _beta(spec, n, rho, eps=eps)

    scale = (b / n) if jnp.ndim(b) == 0 else (b / n)[:, None]
    return scale * xh


def _resolve_decode_method(spec) -> str:
    method = getattr(spec, "decode_method", "auto") or "auto"
    if method == "auto":
        proj = getattr(spec, "projection", None) or "srht"
        return "fused" if proj in ("srht", "subsample") else "gram"
    return method


def decode(spec, key, payloads, n, client_ids=None, chunk_offset=0):
    method = _resolve_decode_method(spec)
    record_decode_route("rand_proj_spatial", method)
    if method == "fused":
        proj = getattr(spec, "projection", None) or "srht"
        if proj == "gauss":
            raise ValueError(
                'decode_method="fused" needs an SRHT or subsample projection '
                "(gauss maps have no FWHT structure) — use gram/direct/auto"
            )
        return _decode_fused(spec, key, payloads, n, client_ids, chunk_offset)
    vals = payloads["vals"]  # (n, C, k)
    norm_sq = payloads.get("norm_sq")  # (n, C) or None
    z = jnp.moveaxis(vals, 0, 1).astype(jnp.float32)  # (C, n, k)
    dec = _decode_one_gram if method == "gram" else _decode_one_direct
    if spec.shared_randomness:
        a = _stack_a(spec, key, n, client_ids=client_ids)
        return dec(spec, n, a, z, norm_sq)

    c = vals.shape[1]

    def per_chunk(chunk_id, z_c, nsq_c):
        a = _stack_a(spec, key, n, chunk_id, client_ids=client_ids)
        nsq = None if norm_sq is None else nsq_c[:, None]
        return dec(spec, n, a, z_c[None], nsq)[0]

    # chunk_offset keys the per-chunk {G_i} draws by GLOBAL chunk position,
    # so an owner's chunk-slice decode re-derives the full decode's maps.
    return jax.vmap(per_chunk)(
        chunk_offset + jnp.arange(c), z,
        jnp.zeros((c, n)) if norm_sq is None else jnp.moveaxis(norm_sq, 0, 1),
    )


def self_decode(spec, key, client_id, payload):
    """Unbiased per-client reconstruction (d/k) G_i^T z_i.

    E[G^T G] = (k/d) I for all three projections (SRHT, subsample, gauss), so
    this is the client's unbiased contribution as the server sees it. With
    projection="subsample" it equals Rand-k's (d/k) scatter bit-for-bit
    (Lemma 4.1), so error feedback composes identically across the pair.
    """
    ckey = base.client_key(key, client_id)
    vals = payload["vals"].astype(jnp.float32)  # (C, k)
    scale = spec.d_block / spec.k
    if spec.shared_randomness:
        g = _g_matrix(spec, _client_draw(spec, ckey))  # (k, d)
        return scale * (vals @ g)
    c = vals.shape[0]
    keys = jax.vmap(base.chunk_key, in_axes=(None, 0))(ckey, jnp.arange(c))
    gs = jax.vmap(lambda kk: _g_matrix(spec, _client_draw(spec, kk)))(keys)
    return scale * jnp.einsum("ck,ckd->cd", vals, gs)


CODEC = base.Codec(encode=encode, decode=decode, self_decode=self_decode)
base.register("rand_proj_spatial", CODEC)
