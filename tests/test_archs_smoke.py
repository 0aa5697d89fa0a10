"""Per-architecture smoke tests: REDUCED config of the same family, one
forward + train-grad step + one-token decode on CPU. Asserts shapes + no
NaNs. Full-size configs are exercised only via the dry-run (no allocation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import (
    abstract_params,
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
    param_axes,
    param_defs,
)

jax.config.update("jax_platform_name", "cpu")

B, S = 2, 32


def _batch(cfg, key):
    if cfg.input_mode == "tokens":
        inputs = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    else:
        inputs = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab_size)
    return {"inputs": inputs, "labels": labels}


@pytest.fixture(scope="module", params=configs.ARCHS)
def arch_setup(request):
    cfg = configs.reduce_for_smoke(configs.get_config(request.param))
    params = init_params(cfg, jax.random.key(0))
    return request.param, cfg, params


def test_param_tree_matches_abstract(arch_setup):
    _, cfg, params = arch_setup
    sds = abstract_params(cfg)
    real = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    assert jax.tree.all(jax.tree.map(lambda a, b: a == b, real, sds))
    axes = param_axes(cfg)
    jax.tree.map(
        lambda x, ax: None if len(ax) == x.ndim else pytest.fail(f"{x.shape} vs {ax}"),
        params, axes,
    )


def test_forward_shapes_no_nans(arch_setup):
    name, cfg, params = arch_setup
    batch = _batch(cfg, jax.random.key(1))
    logits, aux = jax.jit(lambda p, t: forward(p, cfg, t))(params, batch["inputs"])
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all()), name
    assert bool(jnp.isfinite(aux)), name


def test_train_grad_step(arch_setup):
    name, cfg, params = arch_setup
    batch = _batch(cfg, jax.random.key(2))

    @jax.jit
    def step(p, b):
        (loss, m), g = jax.value_and_grad(lambda pp: loss_fn(pp, cfg, b), has_aux=True)(p)
        gnorm = jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in jax.tree.leaves(g)))
        return loss, gnorm

    loss, gnorm = step(params, batch)
    assert bool(jnp.isfinite(loss)) and bool(jnp.isfinite(gnorm)), name
    assert float(loss) > 0
    assert float(gnorm) > 0


def test_decode_step(arch_setup):
    name, cfg, params = arch_setup
    cache = init_cache(cfg, B, seq_len=16)
    if cfg.input_mode == "tokens":
        tok = jnp.array([[1], [2]], jnp.int32)
    else:
        tok = jnp.ones((B, 1, cfg.d_model), jnp.float32)
    pos = jnp.full((B, 1), 3, jnp.int32)
    logits, new_cache = jax.jit(lambda p, c, t, q: decode_step(p, cfg, c, t, q))(
        params, cache, tok, pos
    )
    assert logits.shape == (B, 1, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all()), name
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


def test_prefill_decode_consistency(arch_setup):
    """Greedy logits from full forward at position t == decode-step logits
    after feeding tokens 0..t through the cache path."""
    name, cfg, params = arch_setup
    if cfg.input_mode != "tokens":
        pytest.skip("embeddings-input stub")
    t = 6
    toks = jax.random.randint(jax.random.key(3), (1, t + 1), 0, cfg.vocab_size)
    full_logits, _ = forward(params, cfg, toks)
    cache = init_cache(cfg, 1, seq_len=16)
    logits = None
    for i in range(t + 1):
        logits, cache = decode_step(
            params, cfg, cache, toks[:, i : i + 1], jnp.full((1, 1), i, jnp.int32)
        )
    np.testing.assert_allclose(
        np.asarray(logits[0, 0]), np.asarray(full_logits[0, t]), rtol=5e-2, atol=5e-3
    )


def test_full_config_param_counts():
    """Full configs instantiate abstractly (no allocation) with sane sizes."""
    expect_b = {
        "qwen1.5-32b": (28, 36),
        "deepseek-67b": (62, 72),
        "deepseek-coder-33b": (30, 36),
        "gemma3-4b": (3, 5.5),
        "musicgen-medium": (1.3, 2.2),
        "deepseek-moe-16b": (14, 19),
        "mixtral-8x22b": (130, 150),
        "llava-next-34b": (32, 37),
        "mamba2-130m": (0.1, 0.2),
        "jamba-v0.1-52b": (47, 58),
    }
    for name in configs.ARCHS:
        cfg = configs.get_config(name)
        n = cfg.n_params() / 1e9
        lo, hi = expect_b[name]
        assert lo <= n <= hi, f"{name}: {n:.2f}B params out of [{lo},{hi}]"
        if cfg.n_experts:
            assert cfg.n_params_active() < cfg.n_params()


def test_ssd_grads_finite_over_a_full_chunk():
    """mamba2's 256-step SSD chunk accumulates more than exp's float32 range
    of decay; the masked (acausal) half of the decay matrix must not turn
    that overflow into NaN gradients."""
    from repro.models.mamba import ssd_chunked

    b, l, h, p, n, chunk = 1, 256, 2, 4, 8, 256
    ks = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(ks[0], (b, l, h, p))
    bb = jax.random.normal(ks[1], (b, l, h, n))
    cc = jax.random.normal(ks[2], (b, l, h, n))
    dt = jnp.full((b, l, h), 0.1)
    a_log = jnp.log(jnp.full((h,), 8.0))  # 256 * 8 * 0.1 = 205 >> log(f32 max)

    def loss(x, a_log):
        y, _ = ssd_chunked(x, dt, a_log, bb, cc, jnp.ones((h,)), chunk)
        return jnp.sum(y ** 2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1))(x, a_log)
    assert bool(jnp.isfinite(val))
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
