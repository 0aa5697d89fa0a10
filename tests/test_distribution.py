"""Distribution layer: sharding rules, compressed-mean collective, and an
in-subprocess 8-device mesh lower+compile (keeps the main test process on
1 device as required)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core import codec
from repro.data import SyntheticLM
from repro.dist import collectives
from repro.dist.sharding import MODEL_PREF, spec_for
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.optim import AdamW
from repro.train import make_train_step


class FakeMesh:
    def __init__(self, shape, names):
        self.axis_names = names
        import numpy as _np

        self.devices = _np.empty(shape)


def test_spec_for_divisibility():
    mesh = FakeMesh((16, 16), ("data", "model"))
    # standard attn weight: heads -> model, embed -> data
    assert spec_for((5120, 5120), ("embed", "heads"), mesh) == P("data", "model")
    # non-divisible model dim falls through (3352 % 16 != 0)
    assert spec_for((768, 3352), ("embed", "mamba_inner"), mesh) == P("data", None)
    # experts not divisible (8 % 16) -> ff gets model, embed gets data
    assert spec_for((8, 6144, 16384), ("experts", "embed", "ff"), mesh) == P(None, "data", "model")
    # norm: replicated
    assert spec_for((5120,), (None,), mesh) == P(None)
    # pod axis never assigned to params
    mesh3 = FakeMesh((2, 16, 16), ("pod", "data", "model"))
    assert spec_for((5120, 5120), ("embed", "heads"), mesh3) == P("data", "model")


@pytest.mark.parametrize("axes", [("pod",), ("pod", "data", "model")])
def test_make_mesh_axes_are_auto(axes):
    """The package's meshes take Auto axes, not jax.make_mesh's Explicit
    default: the GSPMD exchange's sharding constraints refuse Explicit."""
    mesh = make_mesh((1,) * len(axes), axes)
    assert mesh.axis_names == axes
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * len(axes)


def test_compressed_mean_identity_is_exact():
    tree = {
        "w": jnp.asarray(np.random.default_rng(0).standard_normal((3, 8, 8)), jnp.float32),
        "b": jnp.asarray(np.random.default_rng(1).standard_normal((3, 5)), jnp.float32),
    }
    spec = codec.build("identity", d_block=64)
    mean, info, _ = collectives.compressed_mean_tree(spec, jax.random.key(0), tree)
    np.testing.assert_allclose(np.asarray(mean["w"]), np.asarray(tree["w"].mean(0)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mean["b"]), np.asarray(tree["b"].mean(0)), rtol=1e-6)
    assert info["n_clients"] == 3


def test_compressed_mean_unbiased_full_budget():
    """k == d_block: SRHT is invertible per client => exact mean recovery."""
    n, d = 4, 64
    tree = {"w": jnp.asarray(np.random.default_rng(2).standard_normal((n, d)), jnp.float32)}
    spec = codec.build("rand_proj_spatial", k=d, d_block=d, transform="max")
    mean, _, _ = collectives.compressed_mean_tree(spec, jax.random.key(1), tree)
    np.testing.assert_allclose(
        np.asarray(mean["w"]), np.asarray(tree["w"].mean(0)), rtol=1e-3, atol=1e-4
    )


def test_dme_train_step_matches_plain_with_identity():
    """dme_step(identity codec) == plain step on the flattened batch."""
    cfg = configs.reduce_for_smoke(configs.get_config("musicgen-medium"))
    opt = AdamW(lr=1e-2, warmup_steps=1)
    params = init_params(cfg, jax.random.key(0))
    n = 2
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, batch=3, n_clients=n)
    batch = data.batch_at(0)
    flat_batch = jax.tree.map(lambda l: l.reshape((-1,) + l.shape[2:]), batch)

    plain = jax.jit(make_train_step(cfg, opt))
    dme = jax.jit(make_train_step(
        cfg, opt, dme_spec=codec.build("identity", d_block=1024)))

    p1, s1, m1 = plain(params, {"opt": opt.init(params)}, flat_batch, 0)
    p2, s2, m2 = dme(params, {"opt": opt.init(params)}, batch, 0)
    # identical up to fp reassociation (client-mean vs batch-mean of grads)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5)


def test_dme_train_step_compressed_converges_direction():
    """Compressed grad must correlate strongly with the true mean grad."""
    cfg = configs.reduce_for_smoke(configs.get_config("musicgen-medium"))
    opt = AdamW(lr=1e-2, warmup_steps=1)
    params = init_params(cfg, jax.random.key(0))
    n = 4
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, batch=2, n_clients=n)
    batch = data.batch_at(0)

    from jax.flatten_util import ravel_pytree
    from repro.models import transformer

    def per_client(b):
        return jax.grad(lambda p: transformer.loss_fn(p, cfg, b)[0])(params)

    grads = jax.vmap(per_client)(batch)
    spec = codec.build("rand_proj_spatial", k=256, d_block=512, transform="avg")
    mean_hat, _, _ = collectives.compressed_mean_tree(spec, jax.random.key(3), grads)
    true_mean = jax.tree.map(lambda g: g.mean(0), grads)
    gh, _ = ravel_pytree(mean_hat)
    gt, _ = ravel_pytree(true_mean)
    cos = float(jnp.dot(gh, gt) / (jnp.linalg.norm(gh) * jnp.linalg.norm(gt)))
    assert cos > 0.5, cos  # 2x compression, 4 clients: strong directional agreement


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.dist import sharding as shard_lib
    from repro.launch import specs
    from repro.optim import AdamW
    from repro.train import make_train_step
    from repro.core import codec

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = configs.reduce_for_smoke(configs.get_config("{arch}")).replace(
        vocab_pad_multiple=32)
    opt = AdamW()
    params = specs.params_specs(cfg, mesh)
    state = {{"opt": specs.opt_state_specs(opt, params)}}
    spec = codec.build("rand_proj_spatial", k=16, d_block=128, use_pallas="never")
    fn = make_train_step(cfg, opt, dme_spec=spec, mesh=mesh, client_axes=("pod",))
    import jax.numpy as jnp
    batch = {{
        "inputs": jax.ShapeDtypeStruct((2, 4, 32), jnp.int32,
            sharding=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("pod", "data", None))),
        "labels": jax.ShapeDtypeStruct((2, 4, 32), jnp.int32,
            sharding=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("pod", "data", None))),
    }}
    step = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = jax.jit(fn).lower(params, state, batch, step).compile()
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {{}}
    print("SUBPROC_OK", ca.get("flops", -1))
    """
)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["musicgen-medium", "deepseek-moe-16b"])
def test_mesh_compile_in_subprocess(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC.format(arch=arch)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert "SUBPROC_OK" in out.stdout, out.stderr[-2000:]
