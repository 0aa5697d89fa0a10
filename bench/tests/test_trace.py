"""The reduction from a profiler trace to busy time, idle gaps and op time."""
from __future__ import annotations

import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "tpu_small.xplane.pb")


def synthetic():
    ms = 1_000_000
    ops = {0: [
        trace.Op(0 * ms, 10 * ms, "fusion.1", "%fusion.1 = f32[8] fusion(...)"),
        trace.Op(10 * ms, 40 * ms, "while.3", "%while.3 = (...) while(...)"),
        trace.Op(12 * ms, 20 * ms, "srht_gram_apply_pallas.7", "custom-call"),
        trace.Op(22 * ms, 30 * ms, "srht_gram_apply_pallas.7", "custom-call"),
        trace.Op(60 * ms, 70 * ms, "fusion.1", "%fusion.1 = f32[8] fusion(...)"),
    ]}
    spans = [(0, 100 * ms, "bench.window"), (40 * ms, 60 * ms, "bench.wait"),
             (42 * ms, 48 * ms, "bench.batch"), (70 * ms, 95 * ms, "bench.dispatch")]
    return trace.Trace(ops=ops, spans=spans, window=(0, 100 * ms), platform="tpu")


def test_busy_is_the_union_of_op_intervals():
    tr = synthetic()
    assert trace.busy_s(tr) == pytest.approx(0.050)
    assert tr.window_s == pytest.approx(0.100)


def test_container_ops_count_as_busy_but_not_as_work():
    tr = synthetic()
    names = [o.name for o in trace.leaf_ops(tr.ops[0])]
    assert "while.3" not in names and names.count("srht_gram_apply_pallas.7") == 2
    secs, calls = trace.op_seconds(tr, "srht_gram_apply")
    assert calls == 2 and secs == pytest.approx(0.016)
    top = dict(trace.top_ops(tr))
    assert top["%fusion.1 = f32[8] fusion(...)"] == pytest.approx(0.020)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = trace.idle_gaps(synthetic())
    assert [g[0] for g in gaps] == ["bench.dispatch", "bench.wait"]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020])


def test_recorded_tpu_trace():
    """A trace recorded on a v5e by ``record_fixture.py``: three calls of a
    program holding two ``srht_gram_apply`` kernels, then a 50 ms sleep.
    The device clock runs about 1.4 ms behind the host spans there, so only
    the third call's six ops fall inside ``bench.window``."""
    tr = trace.reduce(FIXTURE)
    assert tr.platform == "tpu" and list(tr.ops) == [0]
    assert [o.name for o in tr.ops[0]] == [
        "copy-start", "srht_gram_apply_pallas.2", "srht_gram_apply_pallas.3",
        "reduce_sum.14", "copy-done", "convolution_reduce_fusion"]
    secs, calls = trace.op_seconds(tr, "srht_gram_apply")
    assert calls == 2 and secs == pytest.approx(83_243e-9)
    assert trace.busy_s(tr) == pytest.approx(85_745e-9)
    assert tr.window_s == pytest.approx(0.05305957)
    name, longest = trace.idle_gaps(tr)[0]
    assert name == "bench.sleep" and longest >= 0.05
