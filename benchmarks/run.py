"""Benchmark harness: one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--only mse,tasks,fl,systems,roofline]
    PYTHONPATH=src python -m benchmarks.run --smoke   # CI: reduced sizes

Prints ``name,us_per_call,derived`` CSV (teed to results/bench_output.csv)
and writes the same rows as ``results/BENCH_<mode>.json`` so CI can archive
the perf trajectory as a workflow artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def smoke(out: list[str]) -> None:
    """Reduced-size sweep for CI: small (n, k, d), few trials, plus a
    round-trip through the dist layer's compressed-mean collective."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import codec
    from repro.dist import collectives

    from . import bench_systems
    from .common import base_vector_clients, mse_over_trials, rows, timed

    d, n, k = 256, 8, 16
    xs, r = base_vector_clients(n, d, 3, seed=0)
    for name, tf in [("rand_k", "one"), ("rand_k_spatial", "avg"),
                     ("rand_proj_spatial", "avg")]:
        spec = codec.build(name, k=k, d_block=d, transform=tf)
        mse, sec = mse_over_trials(spec, xs, trials=20)
        rows(out, f"smoke/mse_R{r:.1f}/n{n}_k{k}/{name}", sec * 1e6, f"{mse:.4f}")

    bench_systems.walltime(out, n=4, k=16, d=256)
    bench_systems.ownership(out, n=8, k=64, d=128, n_chunks=8)
    bench_systems.fused_kernels(out, n=8, k=32, d=512, n_chunks=4)
    bench_systems.sparseproj_encode(out)  # full-size: the gate needs margin
    bench_systems.quant(out)  # full-size: the MSE + coded<=raw gates need margin

    from . import bench_fl

    bench_fl.smoke(out)

    from . import bench_async

    bench_async.smoke(out)

    # dist-layer round-trip: pytree -> chunked encode -> server decode -> tree
    rng = np.random.default_rng(0)
    tree = {
        "w": jnp.asarray(rng.standard_normal((n, 64, 64)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((n, 96)), jnp.float32),
    }
    for payload_dtype in ("float32", "int8"):
        spec = codec.build("rand_proj_spatial", k=32, d_block=256,
                             transform="avg", payload_dtype=payload_dtype)
        _, info, _ = collectives.compressed_mean_tree(spec, jax.random.key(0), tree)
        fn = jax.jit(
            lambda key, s=spec: collectives.compressed_mean_tree(s, key, tree)[0]
        )
        sec, _ = timed(fn, jax.random.key(0))
        rows(out, f"smoke/dist/compressed_mean_tree/{payload_dtype}", sec * 1e6,
             f"bytes_per_client={info['payload_bytes_per_client']};"
             f"ratio={info['full_bytes'] / info['payload_bytes_per_client']:.1f}x")


def run_metadata(mode: str) -> dict:
    """The provenance stamp every benchmark artifact carries (schema v1):
    enough to reproduce the run and to refuse to compare apples to oranges
    across jax versions / backends / hosts. tools/bench_artifacts.py
    validates its presence before CI uploads anything."""
    import platform

    import jax

    return {
        "mode": mode,
        "jax_version": jax.__version__,
        "jax_backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python_version": platform.python_version(),
        "platform": platform.platform(),
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def write_json(out: list[str], mode: str, secs: float) -> str:
    records = []
    for line in out[1:]:
        name, us, derived = line.split(",", 2)
        records.append({"name": name, "us_per_call": float(us), "derived": derived})
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"BENCH_{mode}.json")
    with open(path, "w") as f:
        json.dump(
            {"schema_version": 1, "mode": mode, "run": run_metadata(mode),
             "total_s": round(secs, 1), "rows": records},
            f, indent=1,
        )
    return path


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="mse,tasks,fl,async,systems,roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-size CI sweep; writes results/BENCH_smoke.json")
    args = ap.parse_args()
    sections = set(args.only.split(","))

    out: list[str] = ["name,us_per_call,derived"]
    t0 = time.time()
    if args.smoke:
        smoke(out)
    else:
        if "mse" in sections:
            from . import bench_mse

            bench_mse.run(out)
        if "tasks" in sections:
            from . import bench_tasks

            bench_tasks.run(out)
        if "fl" in sections:
            from . import bench_fl

            bench_fl.run(out)
        if "async" in sections:
            from . import bench_async

            bench_async.run(out)
        if "systems" in sections:
            from . import bench_systems

            bench_systems.run(out)
        if "roofline" in sections:
            from . import roofline

            roofline.run(out)

    print("\n".join(out))
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "bench_output.csv"), "w") as f:
        f.write("\n".join(out) + "\n")
    secs = time.time() - t0
    path = write_json(out, "smoke" if args.smoke else "full", secs)
    print(f"# total {secs:.1f}s, {len(out)-1} rows -> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
