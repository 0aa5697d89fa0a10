"""Readings that set a cell's limits: the program against the reference on
many seeds, and the control and the faults on a few, in one process.

    python3 bench/study.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--first-seed N] [--seconds S] [--out chiprun_out/study.<cell>.jsonl]

Per seed it sets the cell up as a run does (a training cell's compiled
step is built once and reused), runs a window of ``--seconds`` where the cell's check
samples from a window (FL), and compares the program with the reference.
On the first ``--control-seeds`` seeds it also reads, against the same
reference:

- ``control``: the reference put in the program's place, computed in the
  precision below the configuration's (fp8 matmul operands for the
  bfloat16 training step; bfloat16 throughout for the float32 FL rounds);
- ``control_exchange`` (training): the reference with its model in float32
  and every array of its exchange rounded to bfloat16, one below the
  exchange's float32;
- ``half``: half of the clients left out, the mean taken over the rest;
- ``altered`` (FL): the largest coordinate of each decoded mean negated.

A training step that returns its state unchanged reads 1 on ``update_gap``
by construction, and one whose decoded mean is scaled by s reads |s - 1|
on ``mean_norm_gap``; neither is run. The benchmark's own runs never run this.
Requires the chip, like a run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_100_000_001)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import common, run

    cell = common.resolve_cell(ROOT, args.workload)
    run.configure_cache(ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("study: needs the chip", file=sys.stderr)
        return 3
    driver_mod = common.driver_module(cell)
    out = open(args.out, "a") if args.out else None
    built = None
    for j in range(args.seeds):
        seed = args.first_seed + 7919 * j
        t0 = time.perf_counter()
        d = driver_mod.Cell(cell, seed, devices[:cell.chips])
        d.setup(built)
        built = getattr(d, "built", None)
        rec = {"workload": cell.name, "seed": seed}
        if hasattr(d, "sampled"):            # FL: the check samples blocks of a window
            d.window(args.seconds)
            d.release()
            seeds = d.sampled()
            ref = d.reference_readings(seeds)
            rec["program"] = dict(driver_mod.compare({s: d.means[s] for s in seeds}, ref))
            if j < args.control_seeds:
                for name, kw in (("control", {"lowp": "bfloat16"}),
                                 ("half", {"clients": cell.config["task"]["clients"] // 2}),
                                 ("altered", {"alter": True})):
                    rec[name] = dict(driver_mod.compare(d.reference_readings(seeds, **kw), ref))
        else:
            d.release()
            ref = d.reference_readings()
            rec["program"] = dict(driver_mod.compare(d.program_readings, ref))
            rec["loss"], rec["ref_loss"] = d.program_readings[0].tolist(), ref[0].tolist()
            rec["mean_norm"], rec["ref_mean_norm"] = (d.program_readings[3].tolist(),
                                                      ref[3].tolist())
            rec["leaves"] = d.leaf_names()
            rec["leaf_grad_gaps"] = {"program": driver_mod.leaf_gaps(
                d.program_readings[1], ref[1]).tolist()}
            if j < args.control_seeds:
                for name, kw in (("control", {"mm": "float8_e4m3fn"}),
                                 ("control_exchange", {"lowp": "bfloat16"}),
                                 ("half", {"clients": d.n // 2})):
                    got = d.reference_readings(**kw)
                    rec[name] = dict(driver_mod.compare(got, ref))
                    rec["leaf_grad_gaps"][name] = driver_mod.leaf_gaps(got[1], ref[1]).tolist()
        rec["seconds"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
