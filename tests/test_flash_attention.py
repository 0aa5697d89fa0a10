"""Flash-attention Pallas kernel vs pure-jnp oracle (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas


def _mk(nkv, rep, sq, sk, dh, dtype, seed=0):
    key = jax.random.key(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (nkv * rep, sq, dh), dtype)
    k = jax.random.normal(k2, (nkv, sk, dh), dtype)
    v = jax.random.normal(k3, (nkv, sk, dh), dtype)
    return q, k, v


@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref_causal(rep, dtype):
    q, k, v = _mk(2, rep, 256, 256, 64, dtype)
    got = flash_attention_pallas(q, k, v, rep=rep, q_tile=128, kv_tile=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v, rep=rep)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_flash_sliding_window():
    q, k, v = _mk(1, 2, 256, 256, 64, jnp.float32, seed=1)
    got = flash_attention_pallas(q, k, v, rep=2, window=64, q_tile=64, kv_tile=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, rep=2, window=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-4)


def test_flash_decode_offset():
    """Sq < Sk with q_offset: cross-attention over a prefix (prefill tail)."""
    q, k, v = _mk(2, 1, 128, 512, 128, jnp.float32, seed=2)
    got = flash_attention_pallas(q, k, v, rep=1, q_offset=384, q_tile=128, kv_tile=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v, rep=1, q_offset=384)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("sq,sk,qt,kt", [(128, 384, 64, 128), (512, 512, 256, 64)])
def test_flash_tile_shape_sweep(sq, sk, qt, kt):
    q, k, v = _mk(1, 2, sq, sk, 64, jnp.float32, seed=3)
    got = flash_attention_pallas(q, k, v, rep=2, q_tile=qt, kv_tile=kt, interpret=True)
    want = ref.flash_attention_ref(q, k, v, rep=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-4)


def test_flash_fully_masked_rows_are_finite():
    """q_offset=0 rows attend only to k<=pos; row 0 sees one key — finite."""
    q, k, v = _mk(1, 1, 128, 128, 64, jnp.float32, seed=4)
    got = flash_attention_pallas(q, k, v, rep=1, window=1, q_tile=128, kv_tile=128, interpret=True)
    assert bool(jnp.isfinite(got).all())
