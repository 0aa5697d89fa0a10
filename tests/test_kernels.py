"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracle.

Only the hypothesis-driven sweep at the bottom needs the [test] extra; the
golden/parity tests run everywhere (seeded randomized sweeps with no
third-party dependency live in tests/test_properties_*.py). The fused SRHT
kernels' bitwise golden tests are in tests/test_kernels_srht_fused.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.fwht import (
    _VMEM_TILE_BUDGET,
    _pick_block_rows,
    _split_dims,
    fwht_pallas,
)


@pytest.mark.parametrize("d", [2, 8, 64, 128, 256, 1024, 2048])
def test_fwht_ref_matches_matrix(d):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, d)).astype(np.float32)
    h = ref.hadamard_matrix(d)
    got = np.asarray(ref.fwht_ref(jnp.asarray(x)))
    want = x @ h.T  # H symmetric; explicit anyway
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4 * np.sqrt(d))


@pytest.mark.parametrize("d", [128, 256, 512, 1024, 4096])
@pytest.mark.parametrize("rows", [1, 7, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_pallas_matches_ref(d, rows, dtype):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((rows, d)), dtype)
    got = fwht_pallas(x, interpret=True, block_rows=16)
    want = ref.fwht_ref(x.astype(jnp.float32))
    tol = 1e-4 * d if dtype == jnp.float32 else 0.1 * d
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("d,k", [(256, 16), (1024, 64)])
def test_srht_encode_fused_matches_ref(d, k):
    key = jax.random.key(2)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (5, d))
    signs = jax.random.rademacher(k2, (d,), jnp.float32)
    rows = jax.random.permutation(k3, d)[:k]
    got = ops.srht_encode(x, signs, rows, use_pallas="force")
    want = ref.srht_encode_ref(x, signs, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)


@pytest.mark.parametrize("d,k", [(256, 16), (1024, 64)])
def test_srht_decode_is_adjoint(d, k):
    """<G x, u> == <x, G^T u> for all x, u."""
    key = jax.random.key(3)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.normal(k1, (d,))
    u = jax.random.normal(k2, (k,))
    signs = jax.random.rademacher(k3, (d,), jnp.float32)
    rows = jax.random.permutation(k4, d)[:k]
    gx = ops.srht_encode(x[None], signs, rows)[0]
    gtu = ops.srht_decode(u[None], signs, rows, d)[0]
    np.testing.assert_allclose(
        float(jnp.dot(gx, u)), float(jnp.dot(x, gtu)), rtol=1e-4
    )


def test_srht_rows_matrix_matches_encode():
    d, k = 512, 32
    key = jax.random.key(4)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (d,))
    signs = jax.random.rademacher(k2, (d,), jnp.float32)
    rows = jax.random.permutation(k3, d)[:k]
    g = ops.srht_rows_matrix(signs, rows, d)
    np.testing.assert_allclose(
        np.asarray(g @ x), np.asarray(ops.srht_encode(x[None], signs, rows)[0]),
        rtol=1e-4, atol=1e-5,
    )
    # G G^T has orthogonal-ish rows: diag == k-independent (rows of H have norm sqrt(d))
    np.testing.assert_allclose(np.diag(np.asarray(g @ g.T)), np.ones(k), rtol=1e-5)


# ------------------------------------------------------- FWHT golden tests
# The SRHT encode (G_i = (1/sqrt d) E_i H D_i) underpins every decode-parity
# claim: these pin fwht_pallas against kernels.ref across the non-square
# _split_dims factorisations (d < 128 -> a=1 lane-only; d > 128 -> a=d/128
# Kronecker two-stage), the fused sign flip, and batch rows that do not
# divide the tile height.


def test_split_dims_factorisations():
    assert _split_dims(8) == (1, 8)        # lane-only, b < 128
    assert _split_dims(64) == (1, 64)
    assert _split_dims(128) == (1, 128)
    assert _split_dims(512) == (4, 128)    # two-stage, non-square (a != b)
    assert _split_dims(4096) == (32, 128)
    for bad in (0, 1, 3, 24, 100):
        with pytest.raises(ValueError, match="power of two"):
            _split_dims(bad)


@pytest.mark.parametrize("d", [8, 64, 512, 4096])
@pytest.mark.parametrize("with_signs", [False, True])
def test_fwht_pallas_golden_vs_ref(d, with_signs):
    """scale * H (signs * x) parity across every factorisation shape, with
    the Rademacher flip fused on load (exactly the SRHT encode's form)."""
    rng = np.random.default_rng(d)
    rows = 6
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    signs = jnp.asarray(rng.choice([-1.0, 1.0], size=d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    got = fwht_pallas(x, signs if with_signs else None,
                      with_signs=with_signs, scale=scale, interpret=True,
                      block_rows=8)
    want = ref.fwht_ref((x * signs) if with_signs else x) * scale
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-4 * np.sqrt(d)
    )


@pytest.mark.parametrize("rows", [1, 5, 9, 17])
def test_fwht_pallas_ragged_rows_pad_and_unpad(rows):
    """Batch rows that don't divide the tile height: the pad rows must be
    sliced back off and never leak into the output."""
    d = 256
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    got = fwht_pallas(x, interpret=True, block_rows=8)  # rows % 8 != 0 cases
    assert got.shape == (rows, d)
    want = ref.fwht_ref(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)


def test_pick_block_rows_bounds():
    """The autotuned tile height stays a power of two, >= 8, and within the
    VMEM budget — the contract _pick_block_rows documents: every blocked
    operand's (bt, d) float32 tile, double-buffered, fits the budget."""
    for n_rows, d in [(1, 128), (7, 512), (1000, 4096), (64, 1 << 16),
                      (126_075, 1024)]:
        for n_tiles in (2, 3, 4):
            bt = _pick_block_rows(n_rows, d, n_tiles=n_tiles)
            assert bt >= 8
            assert bt & (bt - 1) == 0
            assert 2 * n_tiles * bt * d * 4 <= _VMEM_TILE_BUDGET or bt == 8
    # a v5e core's scoped VMEM limit is 16 MiB
    assert _VMEM_TILE_BUDGET < 16 * 1024 * 1024
    # and fwht_pallas accepts the default pick end-to-end on a ragged batch
    x = jnp.asarray(np.random.default_rng(0).standard_normal((7, 512)),
                    jnp.float32)
    np.testing.assert_allclose(
        np.asarray(fwht_pallas(x, interpret=True)),
        np.asarray(ref.fwht_ref(x)), atol=1e-3)


@pytest.mark.parametrize("name", [
    "fwht.fwht_pallas", "srht_fused.fwht_rowsigns_pallas",
    "srht_fused.srht_decode_sum_pallas", "srht_fused.srht_gram_apply_pallas",
    "flash_attention.flash_attention_pallas",
])
def test_kernel_wrappers_default_to_compiled(name):
    """A caller that leaves ``interpret`` out gets the compiled kernel; the
    interpreter is only ever asked for by name (tests, ops' CPU route)."""
    import importlib
    import inspect

    module, fn = name.split(".")
    wrapper = getattr(importlib.import_module(f"repro.kernels.{module}"), fn)
    assert inspect.signature(wrapper).parameters["interpret"].default is False


def test_fwht_involution_and_parseval_seeded():
    """H (H x) = d x (involution), ||Hx||^2 = d ||x||^2 (Parseval) — the
    seeded no-dependency version of the hypothesis sweep below."""
    for logd, rows, seed in [(3, 1, 0), (5, 7, 1), (8, 3, 2), (11, 2, 3)]:
        d = 1 << logd
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((rows, d)).astype(np.float32))
        hx = ops.fwht(x)
        hhx = ops.fwht(hx)
        np.testing.assert_allclose(np.asarray(hhx), np.asarray(x) * d,
                                   rtol=2e-3, atol=1e-2 * d)
        np.testing.assert_allclose(
            np.sum(np.asarray(hx) ** 2, -1),
            d * np.sum(np.asarray(x) ** 2, -1), rtol=2e-3
        )


# ------------------------------------------------ hypothesis sweep (optional)
# A plain importorskip would skip the WHOLE module during collection; only
# this sweep needs hypothesis, so it alone is defined conditionally.

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised in the no-extra env
    st = None

if st is not None:

    @settings(max_examples=20, deadline=None)
    @given(
        logd=st.integers(min_value=3, max_value=11),
        rows=st.integers(min_value=1, max_value=9),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_fwht_property_involution_and_parseval(logd, rows, seed):
        """H (H x) = d x (involution), ||Hx||^2 = d ||x||^2 (Parseval)."""
        d = 1 << logd
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((rows, d)).astype(np.float32))
        hx = ops.fwht(x)
        hhx = ops.fwht(hx)
        np.testing.assert_allclose(np.asarray(hhx), np.asarray(x) * d,
                                   rtol=2e-3, atol=1e-2 * d)
        np.testing.assert_allclose(
            np.sum(np.asarray(hx) ** 2, -1),
            d * np.sum(np.asarray(x) ** 2, -1), rtol=2e-3
        )
