"""Fused SRHT kernels (Pallas, interpret=True) against the pure-jnp oracle
in ``kernels/ref.py``, bit for bit, and the ``ops`` dispatch's forced and
never-Pallas routes against each other. The FWHT kernel's tests are in
``test_kernels.py``."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# --------------------------- fused SRHT kernels: BITWISE golden tests
# Small-integer inputs make every +-1-Hadamard partial sum exactly
# representable in float32, and the fused kernels apply scale after the
# transform exactly where ref.py does (docs/KERNELS.md) — so kernel and
# oracle are asserted bit-for-bit equal, not allclose. Reduction order
# cannot matter when all partial sums are exact.


def _ints(rng, shape, hi=8):
    return jnp.asarray(rng.integers(-hi, hi, shape), jnp.float32)


def _signs(rng, shape):
    return jnp.asarray(rng.integers(0, 2, shape) * 2 - 1, jnp.float32)


def _draw_rows(rng, lead, k, d):
    out = np.stack([rng.permutation(d)[:k]
                    for _ in range(int(np.prod(lead)))])
    return jnp.asarray(out.reshape(*lead, k), jnp.int32)


@pytest.mark.parametrize("d", [8, 64, 512, 4096])
@pytest.mark.parametrize("rows", [1, 5, 16])
@pytest.mark.parametrize("sign_pre,sign_post",
                         [(False, False), (True, False), (False, True)])
def test_fwht_rowsigns_golden_bitwise(d, rows, sign_pre, sign_post):
    from repro.kernels.srht_fused import fwht_rowsigns_pallas

    rng = np.random.default_rng(d * 31 + rows)
    x = _ints(rng, (rows, d))
    signs = _signs(rng, (rows, d))
    scale = 0.25  # power of two => scaled sums stay exact
    got = fwht_rowsigns_pallas(x, signs, sign_pre=sign_pre,
                               sign_post=sign_post, scale=scale,
                               block_rows=8, interpret=True)
    want = ref.fwht_rowsigns_ref(x, signs, sign_pre=sign_pre,
                                 sign_post=sign_post, scale=scale)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("d", [8, 64, 512, 4096])
@pytest.mark.parametrize("c", [1, 3, 9])
@pytest.mark.parametrize("shared", [False, True], ids=["per_chunk", "shared"])
def test_srht_decode_sum_golden_bitwise(d, c, shared):
    """Fused decode reduction == scatter -> rowsigns-FWHT -> client sum,
    over ragged chunk grids, shared and per-chunk sign diagonals."""
    from repro.kernels.srht_fused import srht_decode_sum_pallas

    n, k = 3, max(1, d // 4)
    rng = np.random.default_rng(d * 7 + c + shared)
    z = _ints(rng, (n, c, k))
    rows_idx = _draw_rows(rng, (n, c), k, d)
    signs = _signs(rng, (n, 1, d) if shared else (n, c, d))
    scale = 0.125
    u = ref.srht_scatter_ref(z, rows_idx, d)
    got = srht_decode_sum_pallas(u, signs, scale=scale, block_rows=8,
                                 interpret=True)
    # oracle composition (scale placement identical to the kernel):
    t = ref.fwht_rowsigns_ref(u, jnp.broadcast_to(signs, u.shape),
                              sign_post=True, scale=scale)
    want = jnp.sum(t, axis=0)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("d", [8, 64, 512])
@pytest.mark.parametrize("c", [1, 4, 9])
@pytest.mark.parametrize("shared", [False, True], ids=["per_chunk", "shared"])
def test_srht_gram_apply_golden_bitwise(d, c, shared):
    """Fused matrix-free S v: two FWHTs + mask + client sum, bitwise vs the
    oracle (d <= 512: the double transform's partial sums must stay under
    2^24 for exactness, so the 4096 case is covered allclose at ops level)."""
    from repro.kernels.srht_fused import srht_gram_apply_pallas

    n, k = 3, max(1, d // 4)
    rng = np.random.default_rng(d * 13 + c + shared)
    v = _ints(rng, (c, d), hi=4)
    sshape = (n, 1, d) if shared else (n, c, d)
    signs = _signs(rng, sshape)
    mask_rows = _draw_rows(rng, sshape[:2], k, d)
    mask = np.zeros(sshape, np.float32)
    np.put_along_axis(mask, np.asarray(mask_rows), 1.0, axis=-1)
    mask = jnp.asarray(mask)
    # ref's scale is fixed at 1/d — a power of two for power-of-two d
    got = srht_gram_apply_pallas(v, signs, mask, scale=1.0 / d, block_rows=8,
                                 interpret=True)
    want = ref.srht_gram_apply_ref(v, signs, mask)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("d,k", [(64, 16), (4096, 64)])
def test_fused_ops_dispatch_parity(d, k):
    """ops-level fused encode/decode: the forced interpret-mode kernel and
    the jnp oracle composition agree bitwise — integer inputs make both
    transforms exact, and the identical post-scale multiply rounds the
    same way on both paths (this is the use_pallas fallback contract)."""
    n, c = 2, 3
    rng = np.random.default_rng(d + k)
    x = _ints(rng, (n, c, d))
    signs = _signs(rng, (n, c, d))
    rows_idx = _draw_rows(rng, (n, c), k, d)
    enc_force = ops.srht_encode_batch(x, signs, rows_idx, use_pallas="force")
    enc_never = ops.srht_encode_batch(x, signs, rows_idx, use_pallas="never")
    assert (np.asarray(enc_force) == np.asarray(enc_never)).all()

    z = _ints(rng, (n, c, k))
    dec_force = ops.srht_decode_sum(z, signs, rows_idx, d, use_pallas="force")
    dec_never = ops.srht_decode_sum(z, signs, rows_idx, d, use_pallas="never")
    assert (np.asarray(dec_force) == np.asarray(dec_never)).all()

    v = _ints(rng, (c, d), hi=4)
    mask = (ref.srht_scatter_ref(jnp.ones((n, c, k), jnp.float32),
                                 rows_idx, d) > 0).astype(jnp.float32)
    g_force = ops.srht_gram_apply(v, signs, mask, use_pallas="force")
    g_never = ops.srht_gram_apply(v, signs, mask, use_pallas="never")
    np.testing.assert_allclose(np.asarray(g_force), np.asarray(g_never),
                               atol=1e-4 * d)
