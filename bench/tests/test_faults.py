"""The check catches the faults each cell can have, and the control.

Each fault is planted in the program (``faults.py``) underneath a whole run
of a tiny cell on the CPU: the harness drives set-up, window, reference and
comparison as on the chip, and ``correct`` must come out false. The control
is the reference put in the program's place at the precision below the
configuration's: fp8 matmul operands for the bfloat16 training step,
bfloat16 throughout for the float32 FL rounds. It must fail one of the
cell's numbers while the program passes all of them.
"""
from __future__ import annotations

import time

import jax
import pytest

from bench import common, run
from bench.tests import faults

SEED = 5_000_000_029
CASES = [
    ("cpu-mamba.dp4.cpu-rps", "state_unchanged"),
    ("cpu-mamba.dp4.cpu-rps", "half_batch"),
    ("cpu-mamba.dp4.cpu-rps", "mean_scaled"),
    ("cpu-mamba.dp4.cpu-rks", "state_unchanged"),
    ("cpu-mamba.dp4.cpu-rks", "half_batch"),
    ("cpu-mamba.dp4.cpu-rks", "mean_scaled"),
    ("cpu-fl.n10.cpu-fl", "fl_state_unchanged"),
    ("cpu-fl.n10.cpu-fl", "fl_half"),
    ("cpu-fl.n10.cpu-fl", "answer_altered"),
]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_caught(tiny_root, name, fault):
    cell = common.resolve_cell(tiny_root, name)
    with faults.planted(fault):
        res = run.run_cell(cell, SEED, 0.3, False, jax.devices()[:1], time.perf_counter())
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("name", ["cpu-mamba.dp4.cpu-rps", "cpu-mamba.dp4.cpu-rks",
                                  "cpu-fl.n10.cpu-fl"])
def test_control_fails_where_the_program_passes(tiny_root, name):
    cell = common.resolve_cell(tiny_root, name)
    mod = common.driver_module(cell)
    d = mod.Cell(cell, SEED, jax.devices()[:1])
    d.setup()
    limits = {k: v["limit"] for k, v in cell.limits.items()}   # the compared numbers
    if hasattr(d, "sampled"):
        d.window(0.3)
        d.release()
        seeds = d.sampled()
        ref = d.reference_readings(seeds)
        program = mod.compare({s: d.means[s] for s in seeds}, ref)
        control = mod.compare(d.reference_readings(seeds, lowp="bfloat16"), ref)
    else:
        d.release()
        ref = d.reference_readings()
        program = mod.compare(d.program_readings, ref)
        control = mod.compare(d.reference_readings(mm="float8_e4m3fn"), ref)
    assert all(v <= limits[k] for k, v in program if k in limits), program
    assert any(v > limits[k] for k, v in control if k in limits), control
