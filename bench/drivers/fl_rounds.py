"""FL cells: the program's round loop, ``repro.fl.rounds.run_rounds``,
driven closed-loop in blocks of rounds.

Set-up builds the task with ``repro.fl.tasks.power_iteration`` from the
seed's data seed, wraps its ``step`` so that the host clock is read when
each round hands its decoded mean to the task (the end of the round's
server work), and runs one block of rounds to warm every shape. The window
calls ``run_rounds`` with ``rounds_per_block`` rounds and a fresh round
seed per block until ``seconds`` have passed. A configuration whose
``precision`` is ``float32`` runs its rounds under JAX's
``default_matmul_precision("highest")``, the setting a user sets
(``JAX_DEFAULT_MATMUL_PRECISION``) for float32 matmuls: at JAX's default a
float32 matmul on a TPU rounds its operands to bfloat16. A round's wall time runs
from the previous round's end (or the block's start) to its own end;
``fl_round_ms_p95`` is the 95th percentile of every round in the window,
``fl_rounds_per_s`` the rounds over the window.

The check recomputes a sample of the window's blocks, drawn from the seed,
with ``bench.reference.fl_power`` and compares the task's final vector and
the last round's decoded mean (see ``compare``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time

import jax
import numpy as np

from bench import common
from bench.reference import fl_power
from bench.trace import span

SAMPLED_BLOCKS = 4
MATMUL_PRECISION = {"float32": "highest"}   # configuration precision -> JAX's setting


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def v_gap(got, want) -> float:
    """Distance of two unit vectors up to sign (an eigenvector's sign is free)."""
    return float(min(np.linalg.norm(got - want), np.linalg.norm(got + want)))


def mean_gap(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class Cell:
    def __init__(self, spec: common.CellSpec, seed: int, devices):
        self.spec, self.seed, self.devices = spec, seed, devices
        self.task_cfg = spec.config["task"]
        self.mix = spec.mix
        self.block = self.mix["rounds_per_block"]
        self.precision = MATMUL_PRECISION.get(spec.config["precision"])
        self.data_seed = common.sub_seed(seed, "data")
        self.marks = []          # host times: block starts and round ends
        self.means = {}          # block seed -> the last decoded mean seen

    def block_seed(self, j) -> int:
        return common.sub_seed(self.seed, f"block{j}")

    def _timed(self, step):
        def timed_step(state, mean):
            self.marks.append(time.perf_counter())
            self.last_mean = mean
            return step(state, mean)
        return timed_step

    def _run_block(self, round_seed: int):
        from repro.fl import rounds

        prec = (jax.default_matmul_precision(self.precision) if self.precision
                else contextlib.nullcontext())
        with span("bench.block"), prec:
            self.marks.append(None)
            self.marks.append(time.perf_counter())
            state, _ = rounds.run_rounds(
                self.task, self.spec_codec, None,
                rounds.RoundConfig(n_rounds=self.block, seed=round_seed))
        self.means[round_seed] = (np.asarray(state["v"]), np.asarray(self.last_mean))

    def setup(self, built=None):
        from repro.core import codec
        from repro.fl import tasks

        t = self.task_cfg
        task = tasks.power_iteration(n_clients=t["clients"], d=t["d"], samples=t["samples"],
                                     scheme=t["scheme"], seed=self.data_seed)
        self.task = dataclasses.replace(task, step=self._timed(task.step))
        self.spec_codec = codec.build(self.mix["estimator"], **common.codec_kwargs(self.mix))
        self._run_block(self.block_seed("warm"))
        self.marks, self.means = [], {}

    def window(self, seconds: float) -> dict:
        j = 0
        t0 = time.perf_counter()
        with span("bench.window"):
            while time.perf_counter() - t0 < seconds:
                self._run_block(self.block_seed(j))
                j += 1
        elapsed = time.perf_counter() - t0
        times, prev = [], None
        for m in self.marks:
            if m is None:
                prev = None
            elif prev is None:
                prev = m
            else:
                times.append(m - prev)
                prev = m
        times = np.array(times)
        self.blocks = j
        log(f"[window] {j} blocks, {len(times)} rounds in {elapsed:.3f}s; round ms "
            f"median {1e3 * np.median(times):.2f} max {1e3 * times.max():.2f}")
        return {"metrics": {"fl_rounds_per_s": len(times) / elapsed,
                            "fl_round_ms_p95": 1e3 * float(np.percentile(times, 95))},
                "attempted": len(times), "failed": 0,
                "run": {"rounds": len(times), "seconds": elapsed}}

    def release(self):
        self.task = None
        gc.collect()

    def sampled(self) -> list:
        rng = np.random.default_rng(common.sub_seed(self.seed, "sample"))
        picks = rng.choice(self.blocks, size=min(SAMPLED_BLOCKS, self.blocks), replace=False)
        return [self.block_seed(int(j)) for j in sorted(picks)]

    def shards(self):
        t = self.task_cfg
        x = fl_power.image_like(t["samples"], t["d"], self.data_seed)
        return fl_power.iid_shards(x, t["clients"], self.data_seed)

    def reference_readings(self, seeds, lowp=None, clients=None, alter=False):
        shards = self.shards()
        if clients is not None:
            shards = shards[:clients]
        return {s: tuple(np.asarray(a) for a in fl_power.run(
                    shards, self.mix, s, self.block, lowp=lowp, alter=alter))
                for s in seeds}

    def check(self) -> list:
        seeds = self.sampled()
        ref = self.reference_readings(seeds)
        return compare({s: self.means[s] for s in seeds}, ref)


def compare(got: dict, ref: dict) -> list:
    """[(name, value)]: worst over the sampled blocks of the final vector's
    distance (up to sign) and the last decoded mean's relative error."""
    return [
        ("v_gap", max(v_gap(got[s][0], ref[s][0]) for s in ref)),
        ("mean_gap", max(mean_gap(got[s][1], ref[s][1]) for s in ref)),
    ]
