"""Several runs of one cell, one process each, as the check makes them.

    python3 bench/sets.py --workload <cell> --seeds 11,12,13 --seconds 36 \
        [--trace 0|1|0,1] --out chiprun_out/<tag>

Each run is ``bench/run.py`` in a child process (only the child touches
JAX, so it holds the chip alone); its stdout and stderr go to
``<out>.<seed>.<trace>.{out,err}`` and a summary line (exit code, wall time,
the result line) to stdout. ``--trace`` takes one value per run, cycled.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = args.seeds.split(",")
    traces = args.trace.split(",")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    worst = 0
    for i, seed in enumerate(seeds):
        tr = traces[i % len(traces)]
        stem = f"{args.out}.{seed}.{tr}"
        t0 = time.perf_counter()
        with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
            rc = subprocess.call(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload", args.workload,
                 "--seed", seed, "--seconds", args.seconds, "--trace", tr],
                stdout=out, stderr=err)
        with open(stem + ".out") as f:
            lines = f.read().strip().splitlines()
        print(f"{args.workload} seed={seed} trace={tr} rc={rc} "
              f"wall={time.perf_counter() - t0:.1f}s {lines[-1] if lines else ''}", flush=True)
        worst = max(worst, rc)
    return worst


if __name__ == "__main__":
    sys.exit(main())
