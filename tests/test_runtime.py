"""repro.runtime (ISSUE 8): pod plans, hierarchical decode exactness, the
two-tier byte ledger, and real 2-process × 2-pod execution via spawn_local.

The exactness contract under test: ``RoundConfig(hierarchy="hier")`` is
BITWISE identical to the flat path at one pod, and the multi-process run is
bitwise identical to the single-process run at any pod count (every process
decodes its owned pods and learns the rest via the KV exchange, so all
processes hold the same History).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import codec
from repro.fl import Cohort, RoundConfig, get_task, run_rounds
from repro.runtime import (
    PodPlan,
    Topology,
    combine_records,
    combine_rho,
    cross_pod_traffic,
    free_port,
)
from repro.runtime.workers import history_arrays

D = 64


# ------------------------------------------------------------------ pod plan


def test_pod_plan_slices_and_ownership():
    plan = PodPlan(n_clients=10, n_pods=3)
    assert plan.clients_per_pod == 4
    assert [plan.slice_for(p) for p in range(3)] == [(0, 4), (4, 8), (8, 10)]
    assert plan.pod_of(0) == 0 and plan.pod_of(7) == 1 and plan.pod_of(9) == 2
    np.testing.assert_array_equal(plan.clients_of(2), [8, 9])


def test_pod_plan_restrict_preserves_order():
    plan = PodPlan(n_clients=12, n_pods=3)
    ids = np.array([9, 2, 5, 3, 11, 0])
    np.testing.assert_array_equal(plan.restrict(ids, 0), [2, 3, 0])
    np.testing.assert_array_equal(plan.restrict(ids, 2), [9, 11])
    # 1-pod plan: restrict is the identity on any id array (the bitwise
    # exactness contract rides on this)
    one = PodPlan(n_clients=12, n_pods=1)
    np.testing.assert_array_equal(one.restrict(ids, 0), ids)


def test_pod_plan_validation():
    with pytest.raises(ValueError, match="n_pods"):
        PodPlan(n_clients=4, n_pods=0)
    with pytest.raises(ValueError, match="one client per pod"):
        PodPlan(n_clients=2, n_pods=3)
    with pytest.raises(ValueError, match="out of range"):
        PodPlan(n_clients=4, n_pods=2).slice_for(2)


# ------------------------------------------------------------------- combine


def test_combine_records_single_pod_short_circuits():
    est = np.random.default_rng(0).standard_normal((2, D)).astype(np.float32)
    records = {0: {"mean": est, "n": 5}, 1: {"mean": None, "n": 0}}
    combined, n, weights = combine_records(records)
    assert n == 5 and weights == {0: 1.0}
    # unscaled: byte-identical, no *(n/n) float round-trip
    assert combined.tobytes() == est.tobytes()


def test_combine_records_weighted_mean():
    a = np.ones((1, 4), np.float32)
    b = 3 * np.ones((1, 4), np.float32)
    combined, n, weights = combine_records({0: {"mean": a, "n": 1},
                                            1: {"mean": b, "n": 3}})
    assert n == 4 and weights == {0: 0.25, 1: 0.75}
    np.testing.assert_allclose(combined, 2.5 * np.ones((1, 4)), rtol=1e-6)


def test_combine_records_empty():
    combined, n, weights = combine_records({0: {"mean": None, "n": 0}})
    assert combined is None and n == 0 and weights == {}


def test_combine_rho():
    assert combine_rho({0: {"rho": 0.5, "n": 3}}) == 0.5
    got = combine_rho({0: {"rho": 0.2, "n": 1}, 1: {"rho": 0.6, "n": 3}})
    assert abs(got - 0.5) < 1e-12
    assert combine_rho({0: {"rho": None, "n": 3}}) is None


# ---------------------------------------------------------------- byte model


def test_cross_pod_traffic_hier_beats_flat_when_nk_exceeds_d():
    """The regime the hierarchy exists for: n·k payload bytes crossing the
    DCN under flat aggregation exceed the P d-sized estimate exchanges."""
    n, k, d_block = 16, 64, 128
    pipe = codec.as_pipeline(codec.RandProjSpatial(k=k, d_block=d_block,
                                                   transform="avg"))
    cohort = Cohort(n_clients=n)
    plan = PodPlan(n_clients=n, n_pods=2)
    survivors = np.arange(n)
    info = cross_pod_traffic(pipe, cohort, survivors, plan, n_chunks=1)
    assert info["n_pods"] == 2
    assert info["dcn_bytes"] == info["dcn_bytes_hier"]
    assert 0 < info["dcn_bytes_hier"] < info["dcn_bytes_flat"]
    # flat hierarchy ledgers no DCN traffic (single server, one site)
    flat = cross_pod_traffic(pipe, cohort, survivors, plan, n_chunks=1,
                             hierarchy="flat")
    assert flat["dcn_bytes"] == 0


# ---------------------------------------------------------------- topology


def test_topology_from_env_and_validation(monkeypatch):
    from repro.runtime import launch

    assert Topology().n_processes == 1
    with pytest.raises(ValueError):
        Topology(n_processes=2, process_id=5)
    monkeypatch.setenv(launch.ENV_NUM_PROCESSES, "4")
    monkeypatch.setenv(launch.ENV_PROCESS_ID, "2")
    monkeypatch.setenv(launch.ENV_COORDINATOR, "127.0.0.1:1234")
    topo = Topology.from_env()
    assert (topo.n_processes, topo.process_id) == (4, 2)
    assert topo.coordinator == "127.0.0.1:1234"
    assert 0 < free_port() < 65536


# ------------------------------------------- exactness (in-process, 1 pod)


def _drift_setup(n=8, d=2 * D):
    task = get_task("drift", n_clients=n, d=d, rho=0.9, omega=0.05,
                    client_bias=0.5)
    cohort = Cohort(n_clients=n, participation=0.9, dropout=0.2)
    pipe = codec.Pipeline([codec.RandProjSpatial(k=8, d_block=D,
                                                 transform="wavg")])
    return task, cohort, pipe


def _assert_bitwise(ha, hb):
    for key in ha:
        assert ha[key].tobytes() == hb[key].tobytes(), key


def test_hier_one_pod_bitwise_identical_to_flat():
    """RoundConfig(hierarchy="hier", pods=1) reproduces the flat path bit
    for bit — every History column, including the online-R trajectory."""
    task, cohort, pipe = _drift_setup()
    _, h_flat = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=5))
    task, cohort, pipe = _drift_setup()
    _, h_hier = run_rounds(task, pipe, cohort,
                           RoundConfig(n_rounds=5, hierarchy="hier", pods=1))
    _assert_bitwise(history_arrays(h_flat), history_arrays(h_hier))
    assert h_hier.total_dcn_bytes == 0  # one pod: nothing crosses the DCN


def test_hier_two_pods_ledgers_dcn_and_stays_close():
    """pods=2 in one process: the DCN column matches the comms model every
    round, and the two-level estimate tracks the flat one."""
    task, cohort, pipe = _drift_setup()
    cfg = RoundConfig(n_rounds=5, hierarchy="hier", pods=2)
    _, h = run_rounds(task, pipe, cohort, cfg)
    assert len(h.dcn_bytes) == 5
    assert all(b > 0 for b in h.dcn_bytes)
    task, cohort, pipe = _drift_setup()
    _, h_flat = run_rounds(task, pipe, cohort, RoundConfig(n_rounds=5))
    assert h.bytes == h_flat.bytes  # client uplink bytes are plan-invariant
    # two pods estimate from split cohorts: same order of accuracy
    assert np.mean(h.mse) < 4 * np.mean(h_flat.mse) + 1e-3


def test_hier_validation():
    task, cohort, pipe = _drift_setup()
    with pytest.raises(ValueError, match="hierarchy"):
        run_rounds(task, pipe, cohort, RoundConfig(hierarchy="nope"))
    with pytest.raises(ValueError, match="pods"):
        run_rounds(task, pipe, cohort, RoundConfig(hierarchy="hier", pods=0))
    with pytest.raises(ValueError, match="backend"):
        run_rounds(task, pipe, cohort,
                   RoundConfig(hierarchy="hier", pods=2, backend="gspmd"))


def test_spawn_local_refuses_unless_the_cpu_was_asked_for(monkeypatch):
    """On an accelerator host the forked workers would run the rounds on the
    host CPU; spawn_local refuses before forking anything."""
    from repro.runtime import spawn_local
    from repro.runtime.workers import kv_roundtrip_worker

    for value in (None, "tpu"):
        if value is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", value)
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            spawn_local(kv_roundtrip_worker, 2)


# ------------------------------------------ multi-process (slow, subprocess)
#
# spawn_local is exercised from a `python -c` child so the pytest process
# never forks JAX-initialised state; workers live in repro.runtime.workers
# (multiprocessing's spawn context re-imports them by module name).

_COMMON = textwrap.dedent(
    """
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds
    from repro.runtime import spawn_local
    from repro.runtime.workers import (
        build_pipeline, history_arrays, kv_roundtrip_worker, round_worker,
    )

    def local_reference(spec, **over):
        task = get_task(spec["task"], **dict(spec.get("task_kw", {})))
        pipe = build_pipeline(spec["stages"])
        cohort = Cohort(**dict(spec.get("cohort", {})))
        rounds = dict(spec.get("rounds", {}));  rounds.update(over)
        _, hist = run_rounds(task, pipe, cohort, RoundConfig(**rounds))
        return history_arrays(hist), hist

    def assert_bitwise(ha, hb, tag):
        for key in ha:
            assert np.asarray(ha[key]).tobytes() == \
                np.asarray(hb[key]).tobytes(), (tag, key)

    BASE = dict(
        task="drift",
        task_kw=dict(n_clients=8, d=128, rho=0.9, omega=0.05, client_bias=0.5),
        stages=[("rand_proj_spatial", dict(k=8, d_block=64, transform="wavg"))],
        cohort=dict(n_clients=8, participation=0.9, dropout=0.2),
        rounds=dict(n_rounds=3, hierarchy="hier", pods=2),
    )
    """
)

_SUBPROC_PARITY = _COMMON + textwrap.dedent(
    """
    # transport self-test: bit-exact KV roundtrip across 2 real processes
    sums = spawn_local(kv_roundtrip_worker, 2)
    assert sums[0] == sums[1], sums

    # 2 processes x 2 pods == 1 process x 2 pods, bitwise, on every process
    outs = spawn_local(round_worker, 2, args=(BASE,))
    ref, _ = local_reference(BASE)
    for out in outs:
        assert_bitwise(ref, out, f"2proc-2pod p{out['process_id']}")

    # 2 processes x 1 pod == flat single-process, bitwise (process 1 owns
    # no pods and still converges to the same History via the exchange)
    one = dict(BASE, rounds=dict(BASE["rounds"], pods=1))
    outs1 = spawn_local(round_worker, 2, args=(one,))
    flat, _ = local_reference(BASE, hierarchy="flat", pods=1)
    for out in outs1:
        assert_bitwise(flat, out, f"2proc-1pod p{out['process_id']}")

    # DCN tier <= flat all-payloads-to-one-server bytes in the n*k > d
    # regime (acceptance): uplink payload bytes crossing pod boundaries
    # under flat aggregation vs P d-sized estimate exchanges
    big = dict(
        task="drift",
        task_kw=dict(n_clients=16, d=128, rho=0.9, omega=0.05,
                     client_bias=0.5),
        stages=[("rand_proj_spatial",
                 dict(k=64, d_block=128, transform="avg"))],
        cohort=dict(n_clients=16),
        rounds=dict(n_rounds=2, hierarchy="hier", pods=2),
    )
    outs_big = spawn_local(round_worker, 2, args=(big,))
    from repro.runtime import PodPlan, cross_pod_traffic
    pipe = build_pipeline(big["stages"])
    plan = PodPlan(n_clients=16, n_pods=2)
    info = cross_pod_traffic(pipe, Cohort(n_clients=16), np.arange(16),
                             plan, n_chunks=1)
    per_round = outs_big[0]["dcn_bytes"]
    assert (per_round > 0).all()
    assert (per_round <= info["dcn_bytes_flat"]).all(), \
        (per_round, info["dcn_bytes_flat"])
    print("RUNTIME_PARITY_OK", int(outs_big[0]["total_dcn_bytes"]))
    """
)

_SUBPROC_VARIANTS = _COMMON + textwrap.dedent(
    """
    # the decode variants that stress per-pod state: EF residuals,
    # heterogeneous budgets (per-group decode inside each pod), async
    # staleness-1 admission (per-pod stale sub-decode)
    VARIANTS = {
        "ef": dict(BASE, stages=[("top_k", dict(k=8, d_block=64)),
                                 ("error_feedback", dict())]),
        "hetero": dict(BASE, cohort=dict(BASE["cohort"],
                                         budgets=(4, 4, 8, 8, 8, 8, 16, 16))),
        "async": dict(BASE, rounds=dict(BASE["rounds"], async_rounds=True)),
    }
    for tag, spec in VARIANTS.items():
        outs = spawn_local(round_worker, 2, args=(spec,))
        ref, _ = local_reference(spec)
        for out in outs:
            assert_bitwise(ref, out, f"{tag} p{out['process_id']}")
    print("RUNTIME_VARIANTS_OK")
    """
)


def _run_subproc(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, env=env,
    )


_SUBPROC_PSUM = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.runtime import psum_scatter_mean

    mesh = jax.make_mesh((4,), ("pod",))
    rng = np.random.default_rng(0)
    for C in (3, 4, 8):  # ragged and exact chunk tilings
        tiles = jnp.asarray(rng.standard_normal((4, C, 16)), jnp.float32)
        counts = jnp.asarray([2.0, 3.0, 1.0, 4.0])
        got = psum_scatter_mean(tiles, counts, mesh, axis="pod")
        want = np.einsum("p,pcd->cd", np.asarray(counts),
                         np.asarray(tiles)) / 10.0
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-5, atol=1e-6)
    print("PSUM_SCATTER_OK")
    """
)


@pytest.mark.slow
def test_psum_scatter_mean_on_real_mesh():
    """Pre-placed payload tiles reduce to the weighted mean on a 4-device
    mesh, including ragged chunk counts (padded psum_scatter splits)."""
    out = _run_subproc(_SUBPROC_PSUM)
    assert "PSUM_SCATTER_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_two_process_hier_matches_single_process():
    out = _run_subproc(_SUBPROC_PARITY)
    assert "RUNTIME_PARITY_OK" in out.stdout, \
        out.stdout[-1000:] + out.stderr[-2000:]


@pytest.mark.slow
def test_two_process_hier_variants_match_single_process():
    out = _run_subproc(_SUBPROC_VARIANTS)
    assert "RUNTIME_VARIANTS_OK" in out.stdout, \
        out.stdout[-1000:] + out.stderr[-2000:]
