"""Fault-tolerance / substrate tests: checkpoint roundtrip, crash-restore,
elastic client resize, straggler re-normalisation, data determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import codec, mean_estimate
from repro.core import beta as beta_lib
from repro.data import SyntheticLM
from repro.models import init_params
from repro.optim import AdamW
from repro.train import checkpoint as ckpt
from repro.train import make_train_step
from repro.train.supervisor import FaultPlan, Supervisor

jax.config.update("jax_platform_name", "cpu")

CFG = configs.reduce_for_smoke(configs.get_config("mamba2-130m"))
OPT = AdamW(lr=1e-2, warmup_steps=5)


def _mk_supervisor(tmp, n_clients=2, spec=None):
    spec = spec or codec.build("rand_proj_spatial", k=16, d_block=256)

    def make_step(n):
        return jax.jit(make_train_step(CFG, OPT, dme_spec=spec))

    def make_data(n):
        data = SyntheticLM(vocab_size=CFG.vocab_size, seq_len=32, batch=2, n_clients=n)
        return data.batch_at

    def init_state():
        params = init_params(CFG, jax.random.key(0))
        return params, {"opt": OPT.init(params)}

    return Supervisor(
        make_step=make_step, make_data=make_data, init_state=init_state,
        ckpt_dir=str(tmp), n_clients=n_clients, ckpt_every=5, max_restarts=5,
    )


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": {"c": jnp.ones((3, 7), jnp.bfloat16), "d": jnp.zeros((), jnp.int32)}}
    ckpt.save(str(tmp_path), 3, tree)
    like = jax.tree.map(jnp.zeros_like, tree)
    restored, step = ckpt.restore(str(tmp_path), like)
    assert step == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_checkpoint_keep_n_and_crash_safety(tmp_path):
    tree = {"x": jnp.ones(4)}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, tree, keep=2)
    assert ckpt.steps(str(tmp_path)) == [4, 5]
    # a partial tmp dir must be ignored and cleaned
    os.makedirs(tmp_path / "step_000099.tmp_dead", exist_ok=True)
    ckpt.save(str(tmp_path), 6, tree, keep=2)
    assert 99 not in ckpt.steps(str(tmp_path))
    assert not any(".tmp_" in n for n in os.listdir(tmp_path))


def test_supervisor_recovers_from_injected_failures(tmp_path):
    sup = _mk_supervisor(tmp_path / "ck")
    plan = FaultPlan(fail_at_steps=(7, 12))
    params, state, hist = sup.run(16, fault_plan=plan, log_every=1, log_fn=lambda *_: None)
    assert int(state["opt"]["step"]) >= 14  # made it to the end through 2 failures
    assert ckpt.latest_step(str(tmp_path / "ck")) == 15


def test_supervisor_restart_fails_the_main_path_check(tmp_path):
    """A run that only finished through a Supervisor restart is refused by
    the chip smoke's check, even though it reached its last step."""
    from repro import obs

    lines = []
    sup = _mk_supervisor(tmp_path / "ck")
    sup.run(4, fault_plan=FaultPlan(fail_at_steps=(2,)), log_every=1,
            log_fn=lines.append)
    assert any(line.startswith("[step 3]") for line in lines), lines
    faults = obs.main_path_faults({}, lines)
    restart = [f for f in faults if f.startswith("supervisor:")]
    assert len(restart) == 1 and "step 2 failed" in restart[0], faults


def test_supervisor_resume_matches_uninterrupted(tmp_path):
    """Crash-restore must reproduce the uninterrupted trajectory exactly
    (pure-function-of-step data + checkpointed state)."""
    a = _mk_supervisor(tmp_path / "a")
    p_a, s_a, _ = a.run(11, log_fn=lambda *_: None)
    b = _mk_supervisor(tmp_path / "b")
    p_b, s_b, _ = b.run(11, fault_plan=FaultPlan(fail_at_steps=(8,)), log_fn=lambda *_: None)
    for x, y in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6)


def test_elastic_resize(tmp_path):
    sup = _mk_supervisor(tmp_path / "ck", n_clients=4)
    plan = FaultPlan(resize_at={6: 2})
    params, state, _ = sup.run(10, fault_plan=plan, log_fn=lambda *_: None)
    assert sup.n_clients == 2
    assert int(state["opt"]["step"]) == 10


def test_straggler_drop_keeps_unbiasedness():
    """Dropping a straggler = decoding with n_eff; estimator stays unbiased."""
    n, d, k = 6, 128, 8
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal((n, 1, d)), jnp.float32)
    spec = codec.build("rand_proj_spatial", k=k, d_block=d, transform="avg")
    # survivors: first 5 clients; mean target is the survivors' mean
    survivors = xs[:5]
    xbar = np.asarray(jnp.mean(survivors, axis=0))

    @jax.jit
    def one(key):
        return mean_estimate(spec, key, survivors)

    keys = jax.random.split(jax.random.key(1), 400)
    xh = np.asarray(jax.lax.map(one, keys))
    sem = xh.std(0) / np.sqrt(len(xh)) + 1e-4
    assert (np.abs(xh.mean(0) - xbar) < 6 * sem + 5e-3).all()
    # effective re-normalisation beta/n differs between n=6 and n_eff=5
    b6 = beta_lib.srht_beta(6, k, d, 1.0) / 6
    b5 = beta_lib.srht_beta(5, k, d, 1.0) / 5
    assert b6 != pytest.approx(b5)


def test_fl_straggler_renormalizes_by_actual_participants():
    """ISSUE 2 bugcheck: when a sampled client drops, the decoded mean must
    renormalize by the clients that actually reported — NOT the sampled
    count. Wired through fl.rounds with the identity codec, whose decode is
    exact: any 1/n_sampled normalisation would show up as a deterministic
    shrink of the mean."""
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds

    n, d = 8, 128
    task = get_task("dme", n_clients=n, d=d, rho=0.6)
    cohort = Cohort(n_clients=n, participation=1.0, dropout=0.4)
    spec = codec.build("identity", d_block=d)
    _, hist = run_rounds(task, spec, cohort, RoundConfig(n_rounds=8))
    xs = np.asarray(task.aux["xs"])  # (n, d) fixed client vectors

    dropped_any = False
    for t in range(8):
        part = cohort.sample_round(0, t)  # same deterministic draw the driver saw
        assert hist.n_survivors[t] == len(part.survivors)
        true = xs[part.survivors].mean(0)
        # correct decode: exact survivors' mean => recorded mse ~ 0
        assert hist.mse[t] < 1e-9
        if len(part.survivors) < part.n_sampled:
            dropped_any = True
            # the buggy normalisation (sum / n_sampled) is measurably wrong
            buggy = xs[part.survivors].sum(0) / part.n_sampled
            assert float(np.sum((buggy - true) ** 2)) > 1e-3
    assert dropped_any, "dropout=0.4 over 8 rounds never dropped a client"


def test_fl_straggler_renormalizes_with_sparsifying_codec():
    """Same bugcheck through a key-rederiving codec: rand_k with k == d_block
    is an exact (permutation-complete) encode, so the decode over survivors
    must reproduce their exact mean — which only happens when both the
    client_ids and the 1/n_eff normalisation are the survivors'."""
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds

    n, d = 6, 64
    task = get_task("dme", n_clients=n, d=d, rho=0.5)
    cohort = Cohort(n_clients=n, dropout=0.35)
    spec = codec.build("rand_k", k=d, d_block=d)
    _, hist = run_rounds(task, spec, cohort, RoundConfig(n_rounds=6))
    assert any(s < m for s, m in zip(hist.n_survivors, hist.n_sampled))
    assert max(hist.mse) < 1e-8


def test_data_pipeline_determinism_and_noniid():
    data = SyntheticLM(vocab_size=128, seq_len=16, batch=2, n_clients=3, seed=4)
    b1, b2 = data.batch_at(10), data.batch_at(10)
    np.testing.assert_array_equal(np.asarray(b1["inputs"]), np.asarray(b2["inputs"]))
    b3 = data.batch_at(11)
    assert not np.array_equal(np.asarray(b1["inputs"]), np.asarray(b3["inputs"]))
    # non-IID skew shifts client marginals apart
    skew = SyntheticLM(vocab_size=128, seq_len=256, batch=2, n_clients=2, seed=4, non_iid=1.0)
    b = skew.batch_at(0)
    h0 = np.bincount(np.asarray(b["inputs"][0]).ravel(), minlength=128)
    h1 = np.bincount(np.asarray(b["inputs"][1]).ravel(), minlength=128)
    overlap = np.minimum(h0, h1).sum() / h0.sum()
    iid = SyntheticLM(vocab_size=128, seq_len=256, batch=2, n_clients=2, seed=4)
    bi = iid.batch_at(0)
    g0 = np.bincount(np.asarray(bi["inputs"][0]).ravel(), minlength=128)
    g1 = np.bincount(np.asarray(bi["inputs"][1]).ravel(), minlength=128)
    overlap_iid = np.minimum(g0, g1).sum() / g0.sum()
    assert overlap < overlap_iid
