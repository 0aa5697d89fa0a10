"""Pipeline parallelism vs serial reference (4 host devices, subprocess)."""
import sys
import textwrap

import pytest

from support import run_child, src_env

_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.pipeline import partition_blocks, pipeline_apply

    mesh = jax.make_mesh((4,), ("pipe",))
    n_blocks, d, m, mb = 8, 16, 6, 3
    key = jax.random.key(0)
    w = jax.random.normal(key, (n_blocks, d, d)) * 0.3
    b = jax.random.normal(jax.random.fold_in(key, 1), (n_blocks, d)) * 0.1
    params = {"w": w, "b": b}
    x = jax.random.normal(jax.random.fold_in(key, 2), (m, mb, d))

    def block(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def stage_fn(stage_params, h):
        # stage_params: (blocks_per_stage, ...) -> apply sequentially
        def body(hh, p):
            return block(p, hh), None
        hh, _ = jax.lax.scan(body, h, stage_params)
        return hh

    # serial reference
    ref = x
    for i in range(n_blocks):
        ref = block(jax.tree.map(lambda l: l[i], params), ref)

    staged = partition_blocks(params, 4)
    out = pipeline_apply(stage_fn, staged, x, mesh, axis="pipe")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)
    print("PIPELINE_OK", float(jnp.abs(out - ref).max()))
    """
)


@pytest.mark.slow
def test_pipeline_matches_serial():
    out = run_child([sys.executable, "-c", _SUBPROC], timeout=60,
                    env=src_env())
    assert "PIPELINE_OK" in out.stdout, out.stderr[-2000:]


def test_partition_blocks_shapes():
    import jax.numpy as jnp

    from repro.dist.pipeline import partition_blocks

    tree = {"w": jnp.zeros((8, 3, 5)), "b": jnp.zeros((8,))}
    staged = partition_blocks(tree, 4)
    assert staged["w"].shape == (4, 2, 3, 5)
    assert staged["b"].shape == (4, 2)


# ------------------------------------------- pipelined train step (fl stack)


def _smoke_setup(n_clients=0, batch=4):
    import jax

    from repro import configs
    from repro.data import SyntheticLM
    from repro.models import init_params
    from repro.optim import AdamW

    cfg = configs.reduce_for_smoke(configs.get_config("gemma3-4b"))
    opt = AdamW(lr=1e-3, warmup_steps=2)
    params = init_params(cfg, jax.random.key(0))
    data = SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=32, batch=batch,
        n_clients=n_clients, seed=0,
        embed_dim=cfg.d_model if cfg.input_mode == "embeddings" else 0,
    )
    return cfg, opt, params, data.batch_at(0)


def _max_leaf_diff(a, b):
    import jax
    import jax.numpy as jnp

    return max(
        float(jnp.abs(x - y).max())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def test_pipelined_train_step_matches_plain():
    """pipeline_stages=1 routes the loss through pipeline_apply on a 1-device
    'pipe' mesh; the optimizer step must match the unpipelined step."""
    import jax

    from repro.train import make_train_step
    from repro.train.train_step import init_train_state

    cfg, opt, params, batch = _smoke_setup()
    step_ref = jax.jit(make_train_step(cfg, opt))
    mesh = jax.make_mesh((1,), ("pipe",))
    step_pipe = jax.jit(make_train_step(
        cfg, opt, mesh=mesh, pipeline_stages=1, pipeline_microbatches=2))

    p1, _, m1 = step_ref(params, init_train_state(cfg, opt, params), batch, 0)
    p2, _, m2 = step_pipe(params, init_train_state(cfg, opt, params), batch, 0)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-5
    assert _max_leaf_diff(p1, p2) < 2e-5


def test_pipelined_train_step_composes_with_dme():
    """The pipeline shard_map lives inside the per-client vmapped loss."""
    import jax

    from repro.core import codec
    from repro.train import make_train_step
    from repro.train.train_step import init_train_state

    cfg, opt, params, batch = _smoke_setup(n_clients=3, batch=2)
    dme = codec.build("rand_proj_spatial", k=32, d_block=256, transform="avg")
    step_ref = jax.jit(make_train_step(cfg, opt, dme_spec=dme))
    mesh = jax.make_mesh((1,), ("pipe",))
    step_pipe = jax.jit(make_train_step(
        cfg, opt, dme_spec=dme, mesh=mesh, pipeline_stages=1,
        pipeline_microbatches=2))

    st = init_train_state(cfg, opt, params, dme, 3)
    p1, _, m1 = step_ref(params, st, batch, 0)
    st = init_train_state(cfg, opt, params, dme, 3)
    p2, _, m2 = step_pipe(params, st, batch, 0)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-5
    assert _max_leaf_diff(p1, p2) < 2e-5


def test_pipelined_train_step_rejects_bad_configs():
    import jax
    import pytest as _pytest

    from repro.train import make_train_step

    cfg, opt, _, _ = _smoke_setup()
    with _pytest.raises(ValueError, match="mesh"):
        make_train_step(cfg, opt, pipeline_stages=2)
    mesh = jax.make_mesh((1,), ("pipe",))
    with _pytest.raises(ValueError, match="size"):
        make_train_step(cfg, opt, mesh=mesh, pipeline_stages=2)


_SUBPROC_STEP = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.data import SyntheticLM
    from repro.models import init_params
    from repro.optim import AdamW
    from repro.train import make_train_step
    from repro.train.train_step import init_train_state

    cfg = configs.reduce_for_smoke(configs.get_config("gemma3-4b"))
    assert cfg.n_blocks % 2 == 0, cfg.n_blocks
    opt = AdamW(lr=1e-3, warmup_steps=2)
    params = init_params(cfg, jax.random.key(0))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, batch=4,
                       n_clients=0, seed=0,
                       embed_dim=cfg.d_model if cfg.input_mode == "embeddings" else 0)
    batch = data.batch_at(0)

    step_ref = jax.jit(make_train_step(cfg, opt))
    mesh = jax.make_mesh((2,), ("pipe",))
    step_pipe = jax.jit(make_train_step(
        cfg, opt, mesh=mesh, pipeline_stages=2, pipeline_microbatches=4))
    p1, _, m1 = step_ref(params, init_train_state(cfg, opt, params), batch, 0)
    p2, _, m2 = step_pipe(params, init_train_state(cfg, opt, params), batch, 0)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-5
    md = max(float(jnp.abs(a - b).max())
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert md < 2e-5, md
    print("PIPELINE_STEP_OK", md)
    """
)


@pytest.mark.slow
def test_pipelined_train_step_two_stages():
    """Real 2-stage GPipe on 2 host devices vs the unpipelined step."""
    out = run_child([sys.executable, "-c", _SUBPROC_STEP], timeout=150,
                    env=src_env())
    assert "PIPELINE_STEP_OK" in out.stdout, out.stderr[-2000:]
