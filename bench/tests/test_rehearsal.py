"""Every cell of the benchmark, rehearsed end to end on the CPU at tiny sizes.

The tiny root (``tiny.py``) mirrors the real cells: the same drivers,
metric readers and check, smaller configuration files. CPU numbers are
rehearsal readings, not device metrics.
"""
from __future__ import annotations

import time

import jax
import pytest

from bench import common, run

SEED = 4_294_967_311          # above 32 bits, as the check's seeds may be
TRAIN = ["cpu-mamba.dp4.cpu-rps", "cpu-mamba.dp4.cpu-rks"]


def run_tiny(root, name, traced, seed=SEED):
    cell = common.resolve_cell(root, name)
    return run.run_cell(cell, seed, 0.3, traced, jax.devices()[:cell.chips], time.perf_counter())


def check_result(res, cell_metrics):
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == set(cell_metrics)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", TRAIN + ["cpu-fl.n10.cpu-fl"])
def test_cell_end_to_end(tiny_root, name):
    cell = common.resolve_cell(tiny_root, name)
    res = run_tiny(tiny_root, name, traced=False)
    check_result(res, [m["name"] for m in cell.end_to_end])


@pytest.mark.parametrize("name", ["cpu-mamba.dp4.cpu-rps", "cpu-fl.n10.cpu-fl"])
def test_cell_traced(tiny_root, name):
    res = run_tiny(tiny_root, name, traced=True)
    assert res["correct"] is True
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    idle = [k for k in res["metrics"] if k.endswith("device_idle_pct")]
    assert len(idle) == 1 and 0 <= res["metrics"][idle[0]]["value"] <= 100


def test_same_seed_same_inputs(tiny_root):
    a = run_tiny(tiny_root, TRAIN[0], traced=False)
    b = run_tiny(tiny_root, TRAIN[0], traced=False)
    assert a["checks"] == b["checks"]

