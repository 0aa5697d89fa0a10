"""Compiles of the fused SRHT kernels and the select/expand pair for a
described TPU v5e chip.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip that
is described and not attached, and raises what the chip's compiler would
raise (a Mosaic kernel that asks for more VMEM than the core's scoped limit,
a block not aligned to the tiling). Interpret mode sees none of that, so
these compiles guard the kernels' tile sizing at the widths the DME training
step really uses: a mamba2-130m gradient (129.1M parameters) chunked at
d_block = 1024 is C = 126,075 chunks, exchanged by n = 4 clients, each
sending k = 64 coordinates per chunk. One small ragged shape covers padding
of the chunk axis to the tile height, and the select/expand pair's partial
last block.

The topology is described inside a module fixture, never at import: only
one process may load libtpu at a time, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fwht import fwht_pallas
from repro.kernels.select import expand_coords_pallas, select_coords_pallas
from repro.kernels.srht_fused import (
    fwht_rowsigns_pallas,
    srht_decode_sum_pallas,
    srht_gram_apply_pallas,
)

SHAPES = {
    "mamba2_130m": (4, 126_075, 1024),  # (clients, chunks, d_block)
    "ragged": (4, 10, 64),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cases(n, c, d, spec):
    """name -> (kernel closure, argument shapes) for every fused kernel."""
    s = 1.0 / d**0.5
    k = min(64, d // 4)  # the DME step's k = 64 at d = 1024

    def idx(*dims):
        return spec(*dims, dtype=jnp.int32)

    return {
        "fwht": (lambda x: fwht_pallas(x, interpret=False), (spec(c, d),)),
        "fwht_signs": (
            lambda x, sg: fwht_pallas(x, sg, with_signs=True, scale=s,
                                      interpret=False),
            (spec(c, d), spec(d)),
        ),
        "rowsigns": (
            lambda x, sg: fwht_rowsigns_pallas(x, sg, sign_pre=True, scale=s,
                                                interpret=False),
            (spec(n * c, d), spec(n * c, d)),
        ),
        "decode_sum_per_chunk": (
            lambda u, sg: srht_decode_sum_pallas(u, sg, scale=s, interpret=False),
            (spec(n, c, d), spec(n, c, d)),
        ),
        "decode_sum_shared": (
            lambda u, sg: srht_decode_sum_pallas(u, sg, scale=s, interpret=False),
            (spec(n, c, d), spec(n, 1, d)),
        ),
        "gram_per_chunk": (
            lambda v, sg, m: srht_gram_apply_pallas(v, sg, m, scale=1.0 / d,
                                                  interpret=False),
            (spec(c, d), spec(n, c, d), spec(n, c, d)),
        ),
        "gram_shared": (
            lambda v, sg, m: srht_gram_apply_pallas(v, sg, m, scale=1.0 / d,
                                                  interpret=False),
            (spec(c, d), spec(n, 1, d), spec(n, 1, d)),
        ),
        "select": (
            lambda t, r: select_coords_pallas(t, r, interpret=False),
            (spec(n * c, d), idx(n * c, k)),
        ),
        "select_per_client": (  # under the per-client vmap of the Rand-k encode
            jax.vmap(lambda t, r: select_coords_pallas(t, r, interpret=False)),
            (spec(n, c, d), idx(n, c, k)),
        ),
        "expand": (
            lambda z, r: expand_coords_pallas(z, r, d, interpret=False),
            (spec(n * c, k), idx(n * c, k)),
        ),
        "expand_fill": (
            lambda r: expand_coords_pallas(None, r, d, fill=1.0, interpret=False),
            (idx(n * c, k),),
        ),
    }


KERNELS = tuple(_cases(1, 1, 4, lambda *dims, dtype=None: dims))


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, shape, kernel):
    n, c, d = SHAPES[shape]

    def spec(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, args = _cases(n, c, d, spec)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    # the compiled kernel (a Mosaic custom call), not an interpreter loop
    assert "tpu_custom_call" in compiled.as_text()
