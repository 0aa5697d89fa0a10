"""The row-wise select/expand kernels (``kernels/select.py``, interpret
mode through ``use_pallas="force"``) against the XLA expressions they
replace, bit for bit: ``jnp.take_along_axis`` for the select,
``zeros.at[rows].set(z)`` (``ref.srht_scatter_ref``) for the expand. The
values hold -0.0, +-inf, subnormals and magnitudes near float32's limits,
which an arithmetic masked sum would not copy exactly. Then the dispatch by
shape, and the Rand-Proj-Spatial and Rand-k-Spatial decodes with the pair
on the kernel against the pair on XLA."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import codec
from repro.kernels import ops, ref
from repro.kernels.select import (
    expand_coords_pallas,
    select_coords_pallas,
    tile_rows,
)

SELECT, EXPAND = ops.select_coords, ops.expand_coords


@pytest.fixture(autouse=True)
def obs_clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


SPECIALS = np.array([-0.0, np.inf, -np.inf, 3.0e38, -3.0e38, 1.0e-38,
                     -1.0e-45, 1.4e-45, 0.0], np.float32)


def _values(rng, shape):
    """Normal values with the specials planted at random places."""
    x = rng.standard_normal(shape).astype(np.float32)
    flat = x.reshape(-1)
    at = rng.choice(flat.size, size=min(flat.size, 8 * SPECIALS.size),
                    replace=False)
    flat[at] = np.resize(SPECIALS, at.size)
    return jnp.asarray(x)


def _rows(rng, lead, k, d):
    """Distinct indices per row, as the estimators' draws make them."""
    out = np.stack([rng.permutation(d)[:k]
                    for _ in range(int(np.prod(lead)))])
    return jnp.asarray(out.reshape(*lead, k), jnp.int32)


def _bits(a):
    return np.asarray(a).view(np.int32)


def _assert_bitwise(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))


# ------------------------------------------------ kernels, bit for bit


@pytest.mark.parametrize("k", [64, 102])
@pytest.mark.parametrize("n_rows,block_rows", [(40, 8), (37, 16), (5, None)],
                         ids=["tiles", "ragged", "under_one_tile"])
def test_select_golden_bitwise(k, n_rows, block_rows):
    """Grids of whole tiles, a partial last tile, and fewer rows than
    one sublane group."""
    d = 1024
    rng = np.random.default_rng(k * 101 + n_rows)
    t = _values(rng, (n_rows, d))
    rows = _rows(rng, (n_rows,), k, d)
    # every special at least once in the selected slots
    rows = rows.at[0, :SPECIALS.size].set(jnp.arange(SPECIALS.size))
    t = t.at[0, :SPECIALS.size].set(jnp.asarray(SPECIALS))
    got = select_coords_pallas(t, rows, block_rows=block_rows, interpret=True)
    _assert_bitwise(got, jnp.take_along_axis(t, rows, axis=-1))


@pytest.mark.parametrize("k", [64, 102])
@pytest.mark.parametrize("n_rows,block_rows", [(40, 8), (37, 16), (5, None)],
                         ids=["tiles", "ragged", "under_one_tile"])
def test_expand_golden_bitwise(k, n_rows, block_rows):
    d = 1024
    rng = np.random.default_rng(k * 103 + n_rows)
    z = _values(rng, (n_rows, k))
    z = z.at[0, :SPECIALS.size].set(jnp.asarray(SPECIALS))
    rows = _rows(rng, (n_rows,), k, d)
    got = expand_coords_pallas(z, rows, d, block_rows=block_rows,
                               interpret=True)
    _assert_bitwise(got, ref.srht_scatter_ref(z, rows, d))


@pytest.mark.parametrize("n_rows,block_rows", [(40, 8), (37, 16)],
                         ids=["tiles", "ragged"])
def test_expand_fill_golden_bitwise(n_rows, block_rows):
    """A constant fill (the 0/1 mask, hit counts) is the scatter of ones."""
    d, k = 256, 64
    rows = _rows(np.random.default_rng(n_rows), (n_rows,), k, d)
    got = expand_coords_pallas(None, rows, d, fill=1.0,
                               block_rows=block_rows, interpret=True)
    want = ref.srht_scatter_ref(jnp.ones(rows.shape, jnp.float32), rows, d)
    _assert_bitwise(got, want)


# ------------------------------------------- ops: leading dims, vmap, shared


@pytest.mark.parametrize("k", [64, 102])
def test_ops_pair_leading_dims_bitwise(k):
    """(n, C) leading dims through the public ops, forced onto the kernel."""
    n, c, d = 3, 7, 1024
    rng = np.random.default_rng(k)
    t = _values(rng, (n, c, d))
    rows = _rows(rng, (n, c), k, d)
    got = ops.select_coords(t, rows, use_pallas="force")
    _assert_bitwise(got, jnp.take_along_axis(t, rows, axis=-1))
    z = _values(rng, (n, c, k))
    got = ops.expand_coords(z, rows, d, use_pallas="force")
    _assert_bitwise(got, ref.srht_scatter_ref(z, rows, d))
    got = ops.expand_coords(1.0, rows, d, use_pallas="force")
    _assert_bitwise(got, ref.srht_scatter_ref(jnp.ones(rows.shape), rows, d))


def test_ops_pair_vmapped_client_axis_bitwise():
    """The per-client vmap of the Rand-k encode and decode."""
    n, c, d, k = 4, 9, 256, 64
    rng = np.random.default_rng(7)
    t = _values(rng, (n, c, d))
    rows = _rows(rng, (n, c), k, d)
    got = jax.vmap(lambda a, r: ops.select_coords(a, r, use_pallas="force"))(
        t, rows)
    _assert_bitwise(got, jnp.take_along_axis(t, rows, axis=-1))
    z = _values(rng, (n, c, k))
    got = jax.vmap(lambda v, r: ops.expand_coords(v, r, d, use_pallas="force"))(
        z, rows)
    _assert_bitwise(got, ref.srht_scatter_ref(z, rows, d))


def test_ops_pair_shared_rows_bitwise():
    """Shared randomness: one (n, 1, k) draw serves every chunk."""
    n, c, d, k = 3, 6, 512, 64
    rng = np.random.default_rng(11)
    rows = _rows(rng, (n, 1), k, d)
    t = _values(rng, (n, c, d))
    got = ops.select_coords(t, rows, use_pallas="force")
    want = jnp.take_along_axis(t, jnp.broadcast_to(rows, (n, c, k)), axis=-1)
    _assert_bitwise(got, want)
    z = _values(rng, (n, c, k))
    got = ops.expand_coords(z, rows, d, use_pallas="force")
    _assert_bitwise(got, ref.srht_scatter_ref(z, rows, d))
    got = ops.expand_coords(1.0, rows, d, use_pallas="force")
    assert got.shape == (n, 1, d)
    _assert_bitwise(got, ref.srht_scatter_ref(jnp.ones(rows.shape), rows, d))


# ------------------------------------------------------------ dispatch


def _routes(op):
    return {k: v for k, v in obs.snapshot()["counters"].items()
            if k.startswith(f"kernels/dispatch{{op={op},")}


@pytest.mark.parametrize("op", ["select_coords", "expand_coords"])
def test_dispatch_by_shape_on_tpu(monkeypatch, op):
    """On the TPU the kernel from one full tile of rows, XLA below it; each
    decision is counted."""
    tile = tile_rows(1024)
    assert tile % 8 == 0
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    obs.enable()
    assert ops._dispatch(op, tile - 1, "auto", tile=tile) == (False, False)
    assert ops._dispatch(op, tile, "auto", tile=tile) == (True, False)
    assert ops._dispatch(op, 1, "force", tile=tile) == (True, False)
    assert _routes(op) == {
        f"kernels/dispatch{{op={op},route=oracle}}": 1.0,
        f"kernels/dispatch{{op={op},route=pallas}}": 2.0,
    }


def test_dispatch_off_tpu_and_dtypes():
    """Off the TPU "auto" keeps XLA at any size; "force" takes the kernel in
    interpret mode; a non-4-byte dtype stays on XLA even when forced."""
    d, k = 256, 64
    rng = np.random.default_rng(3)
    t = _values(rng, (tile_rows(d) + 8, d))
    rows = _rows(rng, (t.shape[0],), k, d)
    obs.enable()
    ops.select_coords(t, rows)
    ops.select_coords(t[:16], rows[:16], use_pallas="force")
    ops.select_coords(t[:16].astype(jnp.bfloat16), rows[:16],
                      use_pallas="force")
    assert _routes("select_coords") == {
        "kernels/dispatch{op=select_coords,route=oracle}": 2.0,
        "kernels/dispatch{op=select_coords,route=pallas_interpret}": 1.0,
    }


# --------------------------------- decodes: kernel pair against XLA pair


def _route_pair(monkeypatch, route):
    """Send only the select/expand pair down ``route``, leaving every other
    op as the spec asks: the forced FWHT kernels round differently from the
    butterfly oracle, so "force" against "never" would compare those too."""
    monkeypatch.setattr(ops, "select_coords", lambda t, r, use_pallas="auto":
                        SELECT(t, r, use_pallas=route))
    monkeypatch.setattr(ops, "expand_coords", lambda z, r, d, use_pallas="auto":
                        EXPAND(z, r, d, use_pallas=route))


def _exchange(sp, key, xs, lo):
    """Payloads, the decoded mean, an owner's decode of chunks lo: (keyed
    by chunk_offset), and the pair's routes as the dispatch counted them."""
    n = xs.shape[0]
    obs.reset()
    pipe = codec.Pipeline([sp])
    payloads, _ = pipe.encode_all(key, xs)
    vals = payloads.arrays["vals"]
    mean = pipe.decode(key, payloads, n)
    owned = sp.decode(key, {"vals": vals[:, lo:]}, n, chunk_offset=lo)
    routes = {k.split("route=")[1].rstrip("}")
              for k in {**_routes("select_coords"), **_routes("expand_coords")}}
    return vals, mean, owned, routes


@pytest.mark.parametrize("sparsifier", [
    codec.RandProjSpatial(k=16, d_block=128, transform="avg",
                          shared_randomness=False, use_pallas="force"),
    codec.RandKSpatial(k=16, d_block=128, transform="avg",
                       shared_randomness=False),
], ids=["rand_proj_spatial", "rand_k_spatial"])
def test_decode_kernel_pair_matches_xla_pair_bitwise(monkeypatch, sparsifier):
    """The exchange (payloads, the decoded mean, and an owner's chunk slice
    with chunk_offset > 0) with the pair on the kernel equals it with the
    pair on XLA, bit for bit: the pair moves values and computes nothing."""
    xs = jnp.asarray(np.random.default_rng(5).standard_normal((3, 21, 128)),
                     jnp.float32)
    key = jax.random.key(2)
    obs.enable()
    _route_pair(monkeypatch, "force")
    *kernel, routes = _exchange(sparsifier, key, xs, lo=8)
    assert routes == {"pallas_interpret"}
    _route_pair(monkeypatch, "never")
    *xla, routes = _exchange(sparsifier, key, xs, lo=8)
    assert routes == {"oracle"}
    for got, want in zip(kernel, xla):
        _assert_bitwise(got, want)
