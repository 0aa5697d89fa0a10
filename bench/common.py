"""Cell resolution, seeds and file-backed plug-ins shared by the harness.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``. Its files are
found by name under the benchmark root (the directory that holds
``BENCHMARK.json``):

- ``<file>`` of its configuration entry (``bench/configs/<config>.json``);
- ``bench/mixes/<traffic>.json``: the traffic parameters and the ``driver``
  that runs them (``bench/drivers/<driver>.py``);
- ``bench/limits/<cell>.json``: the limit of each number the correctness
  check compares, with the readings it was set from;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class CellSpec:
    """Everything one run of one cell reads from the benchmark's files."""

    root: str
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: str
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, *parts)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(root: str, name: str) -> CellSpec:
    """Read ``BENCHMARK.json`` under ``root`` and the files of cell ``name``.
    Raises ``FileNotFoundError`` / ``KeyError`` for a missing file or name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[cell["config"]]
    return CellSpec(
        root=root,
        name=name,
        chips=int(cell["chips"]),
        config_name=cell["config"],
        config=load_json(os.path.join(root, conf["file"])),
        traffic=cell["traffic"],
        mix=load_json(os.path.join(root, "bench", "mixes", cell["traffic"] + ".json")),
        limits=load_json(os.path.join(root, "bench", "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_module(path: str, name: str):
    """Import a plug-in file by path (names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver_module(cell: CellSpec):
    return load_module(cell.path("bench", "drivers", cell.mix["driver"] + ".py"),
                       "bench_driver_" + cell.mix["driver"])


def metric_reader(cell: CellSpec, metric: str):
    return load_module(cell.path("bench", "metrics", metric + ".py"),
                       "bench_metric_" + metric.replace(".", "_").replace("-", "_"))


def sub_seed(seed: int, purpose: str, bits: int = 31) -> int:
    """A seed for one purpose (weights, data, exchange, ...) drawn from the
    run's ``--seed``, which may exceed 32 bits: distinct seeds and purposes
    give unrelated values that fit ``jax.random.key``."""
    digest = hashlib.sha256(f"{seed}:{purpose}".encode()).digest()
    return int.from_bytes(digest[:8], "little") % (1 << bits)


def codec_kwargs(mix: dict) -> dict:
    """The estimator's fields in a mix, as ``repro.core.codec.build`` takes them."""
    kw = {k: mix[k] for k in ("k", "d_block", "transform", "shared_randomness")}
    if "ridge" in mix:
        kw["ridge"] = mix["ridge"]
    return kw
