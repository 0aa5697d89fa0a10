#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``: process start to the window's start, compilation
included) builds the cell's driver and warms every shape its window uses.
The window then runs for ``--seconds``. With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the JAX profiler and the result carries the per-layer metrics, read
from the trace by ``bench/metrics/<name>.py``, with ``device.busy_s``,
``device.window_s`` and a ``breakdown``. After the window the peak device
memory is read, the program's state is freed and the driver's reference
recomputes what the timed path produced; each compared number is printed
beside its limit (``bench/limits/<cell>.json``; a number with no limit
there is printed as a reading and not compared) as the last lines on
stderr and under ``checks``, the last key of the result: one JSON line, the
last on stdout.

Exit codes: 0 with a result; 2 when a file or the program is missing; 3
when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def configure_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<root>/.cache/jax`` (a fixed path: it is part of the key).
    Every program is cached, however small or quick to compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's traces, its backend compiles and, among those, the
    programs read back from the persistent cache."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.counts = {"traces": 0, "compiles": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def peak_memory(devices, program_bytes: int = 0) -> int:
    """Peak bytes on the fullest device: the larger of the allocator's peak
    (``memory_stats``, which on the TPU counts arrays but not a compiled
    program's temporaries) and the driver's compiled program as XLA lays
    it out (``program_bytes``: arguments, outputs, temporaries, code)."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    return max(peaks + [int(program_bytes)])


def per_layer(cell, tr, run, devices, peak) -> dict:
    from bench import common, trace

    busy = trace.busy_s(tr)
    ctx = {"trace": tr, "busy_s": busy, "window_s": tr.window_s, "chips": len(devices),
           "peak": peak, "run": run, "cell": cell}
    out = {}
    for m in cell.per_layer:
        value = common.metric_reader(cell, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, traced: bool, devices, t_start: float):
    """Set up, measure and check one cell on ``devices``; returns the
    result dict (``checks`` last) and the compared numbers."""
    from bench import common, trace

    dev = devices[0]
    peaks = common.load_json(cell.path("bench", "peaks.json"))
    if dev.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} in bench/peaks.json")
    counter = CompileCounter()
    driver = common.driver_module(cell).Cell(cell, seed, devices)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    before = counter.snapshot()
    if traced:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            with trace.capture(logdir):
                stats = driver.window(seconds)
            tr = trace.reduce(trace.latest_xplane(logdir))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    else:
        stats = driver.window(seconds)
    after = counter.snapshot()
    print(f"bench: set-up {setup_s:.2f}s: {before}; the window: "
          f"{ {k: after[k] - before[k] for k in after} }", file=sys.stderr)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak_memory(devices, getattr(driver, "program_bytes", 0))}
    driver.release()
    t0 = time.perf_counter()
    compared = driver.check()
    print(f"bench: reference and comparison took {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    result = {"correct": None, "attempted": stats["attempted"], "failed": stats["failed"]}
    if traced:
        result["metrics"] = per_layer(cell, tr, stats["run"], devices, peaks[dev.device_kind])
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
    else:
        wanted = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = dict(stats["metrics"], setup_s=setup_s)
        result["metrics"] = {k: {"value": float(values[k]), "unit": u} for k, u in wanted.items()}
    result["device"] = device
    if traced:
        result["breakdown"] = {"device_ops": trace.top_ops(tr), "idle_gaps": trace.idle_gaps(tr)}
    checks, ok = {}, True
    for name, value in compared:
        if name not in cell.limits:
            print(f"bench: reading {name} = {value!r} (not compared: no limit)", file=sys.stderr)
            continue
        limit = float(cell.limits[name]["limit"])
        checks[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    result["correct"] = ok
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: the program is not at {ROOT}/src/repro", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import common

    try:
        cell = common.resolve_cell(ROOT, args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cache = configure_cache(ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    print(f"bench: {args.workload} seed={args.seed} on {cell.chips} x "
          f"{devices[0].device_kind}, compile cache {cache}; JAX up at "
          f"{time.perf_counter() - T_START:.1f}s", file=sys.stderr)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], T_START)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
