"""The observability layer (ISSUE 7): zero-overhead-when-disabled metrics
registry, jit/tracer safety, deterministic counters, and the round-timeline
tracer's byte-ledger parity with History.

The load-bearing contract is the DISABLED case: with obs off (the default),
instrumented code must be bitwise-identical to uninstrumented code on every
backend — observability must never change the math it observes.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import codec
from repro.fl import Cohort, RoundConfig, get_task, run_rounds
from repro.launch.mesh import make_mesh

jax.config.update("jax_platform_name", "cpu")

D = 64


@pytest.fixture(autouse=True)
def obs_clean():
    """Every test starts and ends disabled+empty, with no tracer installed:
    obs state is process-global, so leakage would couple tests."""
    obs.disable()
    obs.reset()
    obs.uninstall_tracer()
    yield
    obs.disable()
    obs.reset()
    obs.uninstall_tracer()


def _pipe():
    return codec.Pipeline([codec.RandProjSpatial(k=8, d_block=D, transform="avg")])


def _run(backend="local", **cfg_kw):
    task = get_task("drift", n_clients=6, d=2 * D)
    cfg = RoundConfig(n_rounds=4, backend=backend, **cfg_kw)
    return run_rounds(task, _pipe(), Cohort(n_clients=6), cfg)


# ------------------------------------------------------------ registry basics


def test_disabled_recording_is_a_noop():
    obs.count("t", "c")
    obs.gauge("t", "g", 3.0)
    obs.observe("t", "h", 1.0)
    obs.marker("t", "m")
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {} and snap["tracer_drops"] == 0
    # the disabled span is one shared object (no per-call allocation) that
    # still yields an annotatable dict
    s1, s2 = obs.span("t", "s"), obs.span("t", "s")
    assert s1 is s2
    with s1 as ann:
        ann["late"] = 1  # must not raise


def test_enabled_recording_and_keys():
    obs.enable()
    obs.count("codec", "decode.calls", sparsifier="rand_k")
    obs.count("codec", "decode.calls", sparsifier="rand_k")
    obs.gauge("bench", "x.compile_us", 12.5)
    obs.observe("fl", "round.duration_us", 3.0)
    with obs.span("fl", "step") as ann:
        ann["note"] = "hi"
    snap = obs.snapshot()
    assert snap["counters"]["codec/decode.calls{sparsifier=rand_k}"] == 2
    assert snap["gauges"]["bench/x.compile_us"] == 12.5
    assert snap["counters"]["fl/step.calls"] == 1
    assert snap["histograms"]["fl/step.duration_us"]["count"] == 1
    obs.reset()
    assert obs.snapshot()["counters"] == {}


def test_registry_is_tracer_safe_under_jit():
    """Recording a traced value inside jit must not leak the tracer, raise,
    or force concretization: the sample is dropped and counted."""
    obs.enable()

    @jax.jit
    def f(x):
        obs.count("t", "dynamic", x)        # tracer -> dropped
        obs.gauge("t", "dyn_gauge", x * 2)  # tracer -> dropped
        obs.count("t", "static", 1)         # python int -> records at trace time
        with obs.span("t", "blk", dyn=x, static_lbl="s") as ann:
            ann["also_dyn"] = x + 1
            y = x * 3.0
        return y

    out = f(jnp.float32(2.0))
    assert float(out) == 6.0
    snap = obs.snapshot()
    assert "t/dynamic" not in snap["counters"]
    assert "t/dyn_gauge" not in snap["gauges"]
    assert snap["counters"]["t/static"] == 1  # once: recorded at trace time
    assert snap["tracer_drops"] >= 3
    # second call hits the jit cache: no re-trace, counters unchanged
    f(jnp.float32(5.0))
    assert obs.snapshot()["counters"]["t/static"] == 1


def test_counters_deterministic_across_runs():
    """Same seed + same config => identical counter snapshots (histograms
    hold wall-clock durations and are exempt by contract)."""
    snaps = []
    for _ in range(2):
        obs.reset()
        obs.enable()
        _run()
        snaps.append(obs.snapshot()["counters"])
        obs.disable()
    assert snaps[0] == snaps[1]
    assert any(k.startswith("fl/client_encode") for k in snaps[0])
    assert any(k.startswith("codec/decode") for k in snaps[0])


# ------------------------------------------- disabled-mode bitwise identity


@pytest.mark.parametrize("backend", ["local", "gspmd", "shard_map"])
def test_disabled_run_bitwise_identical(backend):
    """The acceptance gate: enabling obs (with a tracer installed) and
    running fully disabled produce byte-for-byte identical History metrics —
    instrumentation never perturbs the math."""
    kw = {} if backend == "local" else dict(
        mesh=make_mesh((jax.device_count(),), ("pod",)))

    _, h_off = _run(backend=backend, **kw)

    obs.enable()
    obs.install_tracer(obs.Tracer())
    _, h_on = _run(backend=backend, **kw)
    obs.uninstall_tracer()
    obs.disable()

    for key in ("mse", "mse_pop", "metric", "bytes", "n_survivors"):
        a, b = getattr(h_off, key), getattr(h_on, key)
        np.testing.assert_array_equal(np.asarray(a, dtype=np.float64),
                                      np.asarray(b, dtype=np.float64),
                                      err_msg=f"History.{key} differs on {backend}")


# --------------------------------------------------- tracer + ledger parity


def _spans(tracer):
    return [e for e in tracer.events if e["ph"] == "X"]


def _tracks(tracer):
    names = {e["tid"]: e["args"]["name"] for e in tracer.events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    out = {}
    for e in _spans(tracer):
        out.setdefault(names[e["tid"]], []).append(e)
    return out


def test_trace_covers_every_phase_and_bytes_match_ledger():
    obs.enable()
    tracer = obs.install_tracer(obs.Tracer())
    _, hist = _run()
    obs.uninstall_tracer()

    tracks = _tracks(tracer)
    assert set(obs.PHASES) <= set(tracks), set(obs.PHASES) - set(tracks)
    assert len(tracks["round"]) == 4
    for phase in obs.PHASES:
        rounds_seen = {e["args"]["round"] for e in tracks[phase]}
        assert rounds_seen == {0, 1, 2, 3}, (phase, rounds_seen)

    # THE invariant: trace byte annotations sum exactly to the ledger, and
    # ride only on the wire-crossing tracks
    traced = sum(e["args"]["bytes"] for e in _spans(tracer)
                 if "bytes" in e["args"])
    assert int(traced) == hist.total_bytes == int(np.sum(hist.bytes))
    for track, evs in tracks.items():
        if track in ("client_encode", "stale_admission"):
            continue
        assert not any("bytes" in e["args"] for e in evs), track


def test_trace_json_is_chrome_trace_format(tmp_path):
    obs.enable()
    tracer = obs.install_tracer(obs.Tracer())
    _, hist = _run()
    obs.uninstall_tracer()
    tracer.set_meta("n_rounds", 4)
    tracer.set_meta("ledger_total_bytes", hist.total_bytes)
    path = tmp_path / "trace.json"
    tracer.write(str(path))

    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert all(e["ph"] in ("X", "M", "C") for e in doc["traceEvents"])
    assert doc["metadata"]["ledger_total_bytes"] == hist.total_bytes

    # the CI gate passes on it
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "tools"))
    try:
        import trace_report
        assert trace_report.report(doc) == []
    finally:
        sys.path.pop(0)


def test_history_round_records():
    _, hist = _run()
    recs = hist.round_records()
    assert len(recs) == 4 and recs[0]["round"] == 0
    assert recs[2]["bytes"] == hist.bytes[2]
    assert recs[3]["mse"] == hist.mse[3]


# ------------------------------------------------------------ kernel telemetry


def test_kernel_dispatch_telemetry():
    obs.enable()
    from repro.kernels import ops

    x = jnp.asarray(np.random.default_rng(0).standard_normal((16, D)), jnp.float32)
    ops.fwht(x, use_pallas=False)
    snap = obs.snapshot()
    keys = [k for k in snap["counters"] if k.startswith("kernels/dispatch")]
    assert keys, snap["counters"]
    assert any("op=fwht" in k for k in keys)


def test_cg_iteration_telemetry_outside_jit():
    obs.enable()
    pipe = codec.Pipeline(
        [codec.RandProjSpatial(k=8, d_block=D, transform="avg",
                               decode_method="fused")])
    xs = jnp.asarray(
        np.random.default_rng(0).standard_normal((4, 2, D)), jnp.float32)
    payloads, _ = pipe.encode_all(jax.random.key(0), xs)
    pipe.decode(jax.random.key(0), payloads, 4)  # eager: iters readable
    snap = obs.snapshot()
    assert any(k.startswith("kernels/decode_route") for k in snap["counters"])
    assert "kernels/cg_iters" in snap["histograms"]


# ------------------------------------------- --compare metrics export (CLI)


def test_compare_metrics_json_is_per_run(tmp_path, capsys):
    """--compare + --metrics-json emits ONE merged snapshot with an entry
    per compared run, each holding its OWN counters and round records — the
    schema-v1 regression was last-writer-wins on a single cumulative blob."""
    from repro.fl import run as run_cli

    path = tmp_path / "metrics.json"
    rc = run_cli.main(["--task", "dme", "--compare", "--smoke",
                       "--metrics-json", str(path)])
    assert rc in (0, None)
    data = json.loads(path.read_text())
    assert data["schema_version"] == 2
    labels = [r["estimator"] for r in data["runs"]]
    assert labels == ["rand_k", "rand_k_spatial", "rand_proj_spatial",
                      "sparse_proj"]
    assert data["run"]["estimators"] == labels
    assert data["run"]["n_rounds"] == 12  # 3 smoke rounds x 4 runs
    for entry in data["runs"]:
        assert len(entry["rounds"]) == 3
        encodes = [v for k, v in entry["metrics"]["counters"].items()
                   if "client_encode" in k]
        # each run's snapshot counts ITS 3 rounds, not a running total
        assert encodes and sum(encodes) == 3.0, entry["metrics"]["counters"]


# ---------------------------------------------- main-path check (chip smoke)


def _main_path_counters():
    return {
        "kernels/dispatch{op=srht_encode_batch,route=pallas}": 1.0,
        "kernels/dispatch{op=srht_decode_sum,route=pallas}": 1.0,
        "kernels/decode_route{estimator=rand_proj_spatial,method=fused}": 1.0,
    }


def test_main_path_faults_accepts_the_compiled_path():
    lines = ["[step 0] loss=10.9 (80.1s)", "[step 1] loss=10.8 (0.9s)"]
    assert obs.main_path_faults(_main_path_counters(), lines) == []


def test_main_path_faults_refuses_interpret_mode():
    """A decode forced onto the Pallas interpreter (what a CPU host runs)
    is refused by name, as are runs that recorded no kernel or decode."""
    obs.enable()
    pipe = codec.Pipeline([codec.RandProjSpatial(
        k=8, d_block=D, transform="avg", use_pallas="force")])
    xs = jnp.asarray(np.random.default_rng(0).standard_normal((3, 2, D)),
                     jnp.float32)
    payloads, _ = pipe.encode_all(jax.random.key(0), xs)
    pipe.decode(jax.random.key(0), payloads, 3)
    faults = obs.main_path_faults(obs.snapshot()["counters"])
    assert faults and all("pallas_interpret" in f for f in faults), faults
    assert any("op=srht_decode_sum" in f for f in faults), faults
    assert len(obs.main_path_faults({})) == 2


def test_main_path_faults_refuses_unfused_decode_and_restarts():
    counters = dict(_main_path_counters())
    counters["kernels/decode_route{estimator=rand_proj_spatial,method=gram}"] = 1.0
    lines = ["[supervisor] resumed from step 19", "[step 0] loss=1.0 (1s)"]
    faults = obs.main_path_faults(counters, lines)
    assert len(faults) == 2, faults
    assert "method=gram" in faults[0] and "resumed" in faults[1]
