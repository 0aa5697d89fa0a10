"""What the harness does around a run: files found by name, exit codes."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import jax

from bench import common, run
from bench.tests import tiny


def test_a_cell_added_as_files_runs_without_code_changes(tiny_root, tmp_path):
    root = shutil.copytree(tiny_root, tmp_path / "root")
    cfg = dict(tiny.MODEL, n_layer=1)
    tiny.write_json(root / "bench" / "configs" / "cpu-mamba1.json", cfg)
    tiny.write_json(root / "bench" / "mixes" / "cpu-rps16.json",
                    dict(tiny.MIXES["cpu-rps"], k=16))
    tiny.write_json(root / "bench" / "limits" / "cpu-mamba1.cpu-rps16.json",
                    {k: {"limit": v} for k, v in tiny.LIMITS["train"].items()})
    (root / "bench" / "metrics" / "train.steps_in_window.py").write_text(
        "def read(ctx):\n    return ctx['run']['steps']\n")
    bench = common.load_json(root / "BENCHMARK.json")
    bench["configs"].append({"name": "cpu-mamba1", "source": "rehearsal",
                             "file": "bench/configs/cpu-mamba1.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "cpu-mamba1.cpu-rps16", "config": "cpu-mamba1",
                               "traffic": "cpu-rps16", "chips": 1, "why": "CPU rehearsal"})
    bench["per_layer"].append({"name": "train.steps_in_window", "unit": "steps",
                               "better": "higher", "source": "host_clock", "layer": "device",
                               "moves": "train_tokens_per_s",
                               "workloads": ["cpu-mamba1.cpu-rps16"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "train.device_idle_pct"):
            m["workloads"].append("cpu-mamba1.cpu-rps16")
    tiny.write_json(root / "BENCHMARK.json", bench)
    cell = common.resolve_cell(str(root), "cpu-mamba1.cpu-rps16")
    res = run.run_cell(cell, 7, 0.3, True, jax.devices()[:1], time.perf_counter())
    assert res["correct"] is True
    assert res["metrics"]["train.steps_in_window"]["value"] == res["attempted"]
    assert "train.device_idle_pct" in res["metrics"]


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mamba2-130m.dp4.rps-k64",
         "--seed", "3000000017", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=dict(os.environ, **(env or {})), capture_output=True, text=True,
        timeout=300)


def test_no_chip_means_no_result():
    proc = _run(common.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 3 and proc.stdout == ""


def test_the_benchmark_alone_is_not_a_program(tmp_path):
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(common.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0 and proc.stdout == ""


def test_benchmark_json_names_a_file_for_every_cell():
    bench = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = common.resolve_cell(common.ROOT, w["name"])
        assert os.path.exists(cell.path("bench", "drivers", cell.mix["driver"] + ".py"))
        for m in cell.per_layer:
            assert os.path.exists(cell.path("bench", "metrics", m["name"] + ".py"))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert all(m["moves"] in names for m in cell.per_layer)
    assert json.dumps(bench)
