"""Paper-fidelity tests for the estimator family.

Validates (against the paper's own claims):
  - unbiasedness of every unbiased estimator (statistical)
  - Eq. 1   : Rand-k MSE == (1/n^2)(d/k - 1) sum ||x_i||^2
  - Thm 4.3 : Rand-Proj-Spatial(Max) MSE ~= (d/nk - 1)||x||^2 (identical vecs)
  - Thm 4.4 : Rand-Proj-Spatial(T==1) MSE == Rand-k MSE (orthogonal vecs)
  - Lemma 4.1: projection="subsample" reproduces Rand-k-Spatial exactly
  - Gram decode == paper-literal direct decode (our docs/DESIGN.md §3.3 claim)
  - App. A.1: same rotation for all clients gives no improvement
"""
import functools
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import chunking, codec, correlation, mean_estimate
from repro.core import beta as beta_lib
from repro.core.estimators import decode, encode_all
from support import src_env

jax.config.update("jax_platform_name", "cpu")


def run_trials(spec, xs, trials=200, seed=0):
    """Return (mean_estimates (t, C, d), mse (t,))."""
    xbar = jnp.mean(xs, axis=0)

    @jax.jit
    def one(key):
        xh = mean_estimate(spec, key, xs)
        return xh, correlation.mse(xh, xbar)

    keys = jax.random.split(jax.random.key(seed), trials)
    xhs, mses = jax.lax.map(one, keys)
    return np.asarray(xhs), np.asarray(mses)


def make_clients(kind, n, d, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "identical":
        x = rng.standard_normal(d)
        xs = np.tile(x, (n, 1))
    elif kind == "orthogonal":
        q, _ = np.linalg.qr(rng.standard_normal((d, n)))
        xs = q.T * np.sqrt(d)
    else:
        xs = rng.standard_normal((n, d))
    xs = xs / np.linalg.norm(xs, axis=1, keepdims=True)  # unit norm as in paper
    return jnp.asarray(xs[:, None, :], jnp.float32)  # (n, C=1, d)


UNBIASED = [
    ("rand_k", {}),
    ("rand_k_spatial", {"transform": "avg"}),
    ("rand_proj_spatial", {"transform": "avg"}),
    ("rand_proj_spatial", {"transform": "max"}),
    ("wangni", {}),
    ("induced", {}),
]


@pytest.mark.parametrize("name,kw", UNBIASED, ids=[f"{n}-{v.get('transform','')}" for n, v in UNBIASED])
def test_unbiasedness(name, kw):
    n, d, k = 8, 128, 8
    xs = make_clients("generic", n, d)
    spec = codec.build(name, k=k, d_block=d, **kw)
    xhs, _ = run_trials(spec, xs, trials=600)
    xbar = np.asarray(jnp.mean(xs, axis=0))
    err = np.abs(xhs.mean(0) - xbar)
    # sem-scaled tolerance: estimator std / sqrt(trials)
    sem = xhs.std(0) / np.sqrt(xhs.shape[0]) + 1e-4
    assert (err < 6 * sem + 5e-3).all(), float(err.max())


def test_rand_k_mse_matches_eq1():
    n, d, k = 8, 128, 8
    xs = make_clients("generic", n, d)
    spec = codec.build("rand_k", k=k, d_block=d)
    _, mses = run_trials(spec, xs, trials=1500)
    norm_sq = float(jnp.sum(xs.astype(jnp.float32) ** 2))
    want = (1 / n**2) * (d / k - 1) * norm_sq
    got = mses.mean()
    assert abs(got - want) / want < 0.12, (got, want)


def test_thm_4_3_full_correlation():
    """Identical vectors, T=id ('max'): MSE ~= (d/(nk) - 1) ||x||^2."""
    n, d, k = 8, 128, 8
    xs = make_clients("identical", n, d)
    spec = codec.build("rand_proj_spatial", k=k, d_block=d, transform="max")
    _, mses = run_trials(spec, xs, trials=400)
    norm_sq = float(jnp.sum(xs[0].astype(jnp.float32) ** 2))
    want = (d / (n * k) - 1) * norm_sq
    got = mses.mean()
    assert abs(got - want) / want < 0.15, (got, want)
    # strictly better than Rand-k (paper App. C.2, delta << 2/3):
    # here (d/(nk)-1) / ((1/n)(d/k-1)) = 8/15, so ~1.9x better:
    rand_k_mse = (1 / n) * (d / k - 1) * norm_sq
    assert got < rand_k_mse * 0.7


def test_thm_4_4_no_correlation():
    """Orthogonal vectors, T==1 ('one'): MSE == Rand-k's Eq. 1."""
    n, d, k = 8, 128, 8
    xs = make_clients("orthogonal", n, d)
    spec = codec.build("rand_proj_spatial", k=k, d_block=d, transform="one")
    _, mses = run_trials(spec, xs, trials=1000)
    norm_sq = float(jnp.sum(xs.astype(jnp.float32) ** 2))
    want = (1 / n**2) * (d / k - 1) * norm_sq
    assert abs(mses.mean() - want) / want < 0.12, (mses.mean(), want)


def test_lemma_4_1_subsample_recovers_rand_k_spatial():
    """Rand-Proj-Spatial with E_i == Rand-k-Spatial, same key => exact match."""
    n, d, k = 6, 64, 4
    xs = make_clients("generic", n, d)
    key = jax.random.key(7)
    s_proj = codec.build(
        "rand_proj_spatial", k=k, d_block=d, transform="avg",
        projection="subsample", decode_method="direct",
    )
    s_spatial = codec.build("rand_k_spatial", k=k, d_block=d, transform="avg")
    # NOTE: identical randomness requires identical index derivation; both
    # derive rows via permutation(client_key)[:k], so payload contents match.
    a = mean_estimate(s_proj, key, xs)
    b = mean_estimate(s_spatial, key, xs)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seed", range(6))
def test_lemma_4_1_property_over_seeds(seed):
    """Property test (ISSUE 2): for ANY seed/key/data draw, Rand-Proj-Spatial
    with projection='subsample' matches Rand-k-Spatial's decode exactly —
    shared-randomness and per-chunk modes, gram and direct decode paths."""
    n, d, k = 5, 64, 4
    rng = np.random.default_rng(100 + seed)
    xs = jnp.asarray(rng.standard_normal((n, 2, d)), jnp.float32)
    key = jax.random.key(1000 + seed)
    for shared in (True, False):
        for method in ("direct", "gram"):
            s_proj = codec.build(
                "rand_proj_spatial", k=k, d_block=d, transform="avg",
                projection="subsample", decode_method=method,
                shared_randomness=shared,
            )
            s_spatial = codec.build(
                "rand_k_spatial", k=k, d_block=d, transform="avg",
                shared_randomness=shared,
            )
            a = mean_estimate(s_proj, key, xs)
            b = mean_estimate(s_spatial, key, xs)
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4,
                err_msg=f"shared={shared} method={method}",
            )


def test_lemma_4_1_under_error_feedback():
    """Lemma 4.1 extends through error feedback: the subsample projection's
    (d/k) G^T z self-decode IS Rand-k's (d/k) scatter, so means AND residual
    trajectories coincide over multiple EF rounds."""
    from repro.dist import collectives

    n, d, k = 4, 64, 4
    rng = np.random.default_rng(9)
    tree = {"w": jnp.asarray(rng.standard_normal((n, d)), jnp.float32)}
    s_proj = codec.build(
        "rand_proj_spatial", k=k, d_block=d, transform="avg",
        projection="subsample", decode_method="direct", ef=True,
    )
    s_spatial = codec.build("rand_k_spatial", k=k, d_block=d,
                              transform="avg", ef=True)
    ef_a = ef_b = jnp.zeros((n, 1, d))
    for t in range(4):
        key = jax.random.fold_in(jax.random.key(11), t)
        mean_a, _, ef_a = collectives.compressed_mean_tree(
            s_proj, key, tree, ef_chunks=ef_a
        )
        mean_b, _, ef_b = collectives.compressed_mean_tree(
            s_spatial, key, tree, ef_chunks=ef_b
        )
        np.testing.assert_allclose(
            np.asarray(mean_a["w"]), np.asarray(mean_b["w"]),
            rtol=2e-3, atol=2e-4, err_msg=f"round {t} mean",
        )
        np.testing.assert_allclose(
            np.asarray(ef_a), np.asarray(ef_b), rtol=2e-3, atol=2e-4,
            err_msg=f"round {t} residual",
        )


def test_gram_decode_equals_direct_decode():
    n, d, k = 5, 64, 4
    xs = make_clients("generic", n, d)
    key = jax.random.key(3)
    for transform in ("one", "max", "avg"):
        sg = codec.build("rand_proj_spatial", k=k, d_block=d,
                           transform=transform, decode_method="gram")
        sd = sg.replace(decode_method="direct")
        a = mean_estimate(sg, key, xs)
        b = mean_estimate(sd, key, xs)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_gram_decode_equals_direct_decode_per_chunk_and_est():
    n, d, k = 5, 64, 4
    xs = jnp.asarray(np.random.default_rng(5).standard_normal((n, 3, d)), jnp.float32)
    key = jax.random.key(4)
    sg = codec.build("rand_proj_spatial", k=k, d_block=d, r_mode="est",
                       shared_randomness=False, decode_method="gram")
    sd = sg.replace(decode_method="direct")
    a = mean_estimate(sg, key, xs)
    b = mean_estimate(sd, key, xs)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_varying_correlation_ordering():
    """Given R, Rand-Proj-Spatial(Opt) < Rand-k-Spatial(Opt) < Rand-k (Fig. 3).

    Paper §4.3 simulation setup: clients hold canonical base vectors; the
    number of clients sharing a vector sets R. Same round keys across
    estimators => rand_k vs rand_k_spatial is a PAIRED comparison (identical
    payloads, different decode), which separates the small gap cleanly.
    """
    n, d, k = 8, 256, 24
    base_vecs = np.eye(d)[:2]
    assign = np.array([0, 0, 0, 0, 0, 0, 1, 1])  # R = (6*5 + 2*1)/8 = 4.0
    xs = jnp.asarray(base_vecs[assign][:, None, :], jnp.float32)
    r = float(correlation.r_exact(xs))
    assert r == pytest.approx(4.0)
    res = {}
    for name, tf in [("rand_k", "one"), ("rand_k_spatial", "opt"), ("rand_proj_spatial", "opt")]:
        spec = codec.build(name, k=k, d_block=d, transform=tf, r_value=r)
        _, res[name] = run_trials(spec, xs, trials=600, seed=2)
    paired = res["rand_k"] - res["rand_k_spatial"]
    sem = paired.std() / np.sqrt(len(paired))
    assert paired.mean() > 1.5 * sem, (paired.mean(), sem)  # spatial beats rand_k
    assert res["rand_proj_spatial"].mean() < res["rand_k_spatial"].mean() * 0.99


def test_same_rotation_no_gain_appendix_a1():
    """Pre-rotating every client by the SAME orthonormal G leaves Rand-k MSE unchanged."""
    n, d, k = 8, 128, 8
    xs = make_clients("generic", n, d, seed=3)
    from repro.kernels import ref as kref

    h = kref.hadamard_matrix(d) / np.sqrt(d)  # orthonormal rotation
    dsigns = np.sign(np.random.default_rng(0).standard_normal(d))
    g = h * dsigns[None, :]
    xs_rot = jnp.einsum("ncd,ed->nce", xs, jnp.asarray(g, jnp.float32))
    spec = codec.build("rand_k", k=k, d_block=d)
    _, m_plain = run_trials(spec, xs, trials=800)
    _, m_rot = run_trials(spec, xs_rot, trials=800, seed=1)
    # rotation is an isometry; decoded-back MSE identical in distribution
    assert abs(m_plain.mean() - m_rot.mean()) / m_plain.mean() < 0.1


def test_beta_closed_forms():
    n, k, d = 8, 8, 128
    # rho=0 -> d/k exactly (tr(S) = nk)
    assert beta_lib.srht_beta(n, k, d, 0.0) == pytest.approx(d / k)
    # rho=1 -> d/k * nk/E[rank] ~= d/k (full rank w.h.p.); the theorem's
    # effective d/(nk) scale is beta/n with our x_hat = (beta/n)(...) convention.
    assert beta_lib.srht_beta(n, k, d, 1.0) == pytest.approx(d / k, rel=0.02)
    # rand-k-spatial closed form at rho=1: beta = n/(1-(1-k/d)^n)
    got = float(beta_lib.rand_k_spatial_beta(n, k, d, 1.0))
    want = n / (1 - (1 - k / d) ** n)
    assert got == pytest.approx(want, rel=1e-4)
    # rho=0 -> d/k (recovers Rand-k scaling)
    assert float(beta_lib.rand_k_spatial_beta(n, k, d, 0.0)) == pytest.approx(d / k, rel=1e-5)


def test_rank_s_full_whp():
    """Paper App. C.3: rank(S) == nk with high probability."""
    n, k, d = 8, 8, 128
    bank = beta_lib.srht_eig_bank(n, k, d, trials=64, seed=1)
    ranks = (bank > 1e-4).sum(axis=1)
    assert (ranks == n * k).mean() > 0.95


_BANK_CHILD = """
import os, pathlib, sys, time
import numpy as np
from repro.core import beta
root = pathlib.Path(os.environ["REPRO_BETA_CACHE"])
simulate = beta._simulate_bank
def counted(*args):
    with open(root / "simulated.log", "a") as f:
        f.write(sys.argv[1] + "\\n")
    time.sleep(1.0)  # keep the bank missing while the other process asks
    return simulate(*args)
beta._simulate_bank = counted
(root / f"ready{sys.argv[1]}").touch()
while len(list(root.glob("ready*"))) < 2:
    time.sleep(0.01)
np.save(root / f"bank{sys.argv[1]}.npy", beta.srht_eig_bank(2, 4, 16))
"""


def test_eig_bank_is_simulated_once_across_processes(tmp_path):
    """Two processes ask for the same new bank at once: one simulates it
    under the bank's file lock, the other waits and loads the file, which
    lands whole; both get the same array."""
    env = src_env(REPRO_BETA_CACHE=str(tmp_path), JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", _BANK_CHILD, str(i)],
                              env=env, stderr=subprocess.PIPE, text=True)
             for i in range(2)]
    try:
        errs = [p.communicate(timeout=60)[1] for p in procs]
    finally:
        for p in procs:
            p.kill()
    assert [p.returncode for p in procs] == [0, 0], errs
    assert len((tmp_path / "simulated.log").read_text().split()) == 1
    banks = [np.load(tmp_path / f"bank{i}.npy") for i in range(2)]
    assert banks[0].shape == (beta_lib.default_trials(2, 4), 8)
    np.testing.assert_array_equal(banks[0], banks[1])
    assert [p.name for p in tmp_path.glob("*.npz")] == \
        ["srht_eigs_n2_k4_d16_t512_s0.npz"]


def test_chunking_roundtrip():
    rng = np.random.default_rng(0)
    for d_flat in (5, 64, 100, 1030):
        x = jnp.asarray(rng.standard_normal(d_flat), jnp.float32)
        xc = chunking.chunk(x, 64)
        np.testing.assert_array_equal(np.asarray(chunking.unchunk(xc, d_flat)), np.asarray(x))


def test_tree_chunk_restore():
    tree = {"a": jnp.arange(7, dtype=jnp.float32), "b": (jnp.ones((3, 5)),)}
    xc, restore = chunking.tree_chunk(tree, 16)
    back = restore(xc)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.arange(7, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(back["b"][0]), np.ones((3, 5), np.float32))
