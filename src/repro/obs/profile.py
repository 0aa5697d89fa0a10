"""Profiling hooks: ``jax.profiler`` session wiring + kernel telemetry.

Two kinds of hook live here, both no-ops unless explicitly requested:

- ``profiler_session(logdir)``: context manager around
  ``jax.profiler.start_trace`` / ``stop_trace``. The resulting TensorBoard/
  Perfetto-XL profile is the *device*-level view (XLA ops, fusion, HBM);
  the ``repro.obs.trace`` round tracer is the *system*-level view (phases,
  bytes). Wired to ``repro.fl.run --profile-dir``. Degrades to a plain
  pass-through (with one warning) where the profiler is unavailable —
  profiling is observability, never a hard dependency.

- kernel-dispatch telemetry: ``record_dispatch`` (which route
  ``kernels.ops._should_use_pallas`` took per op), ``record_decode_route``
  (fused / gram / direct per rand_proj_spatial decode), and
  ``record_cg_iters`` (iterations the fused resolvent CG actually ran).
  Dispatch decisions are Python-level statics, so they record under jit
  (once per trace — i.e. per compilation); CG iterations are data-dependent
  and therefore recorded only on eager executions (under jit the sample is
  a tracer and the registry drops it — the tracer-safety contract).
  ``main_path_faults`` reads those counters back (plus the Supervisor's log)
  and names every way a run left the compiled kernel path.
"""
from __future__ import annotations

import contextlib
import warnings

from . import registry


@contextlib.contextmanager
def profiler_session(logdir: str | None):
    """Wrap a block in a ``jax.profiler`` trace writing to ``logdir``; a
    None logdir (or an unavailable profiler) is a pass-through."""
    if logdir is None:
        yield
        return
    import jax

    try:
        jax.profiler.start_trace(logdir)
    except Exception as e:  # profiler backends vary by install
        warnings.warn(f"jax.profiler unavailable ({e}); continuing unprofiled")
        yield
        return
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def record_dispatch(op: str, use_kernel: bool, interpret: bool) -> None:
    """Count one ``_should_use_pallas`` decision for ``op``."""
    if not registry.enabled():
        return
    route = ("pallas_interpret" if use_kernel and interpret
             else "pallas" if use_kernel else "oracle")
    registry.count("kernels", "dispatch", op=op, route=route)


_KERNEL_OPS = ("fwht", "srht_")


def main_path_faults(counters: dict, log_lines=()) -> list[str]:
    """Why a run did not take the compiled main path; empty when it did.

    ``counters`` is ``snapshot()["counters"]`` of a run with the registry
    enabled; ``log_lines`` are the lines a ``train.Supervisor`` logged. The
    run is refused when:

    - no ``fwht``/``srht_*`` dispatch was recorded, or one took a route
      other than ``pallas`` (the interpreter, or the jnp oracle);
    - no ``rand_proj_spatial`` decode was recorded, or one was not ``fused``;
    - the Supervisor restarted a step or resumed from a checkpoint.

    A Supervisor with ``max_restarts=0`` re-raises its first step failure
    instead of logging a restart, so for such a run only the ``resumed``
    line can show here; the ``failed`` line is what a run that retries logs.
    """
    labels = registry.labels_of
    faults = []
    dispatch = [k for k in counters if k.startswith("kernels/dispatch{")
                and labels(k).get("op", "").startswith(_KERNEL_OPS)]
    if not dispatch:
        faults.append("no fwht/srht_* kernel dispatch was recorded")
    faults += [f"{k} took route {labels(k)['route']!r}, not 'pallas'"
               for k in dispatch if labels(k)["route"] != "pallas"]
    routes = [k for k in counters if k.startswith("kernels/decode_route{")
              and labels(k).get("estimator") == "rand_proj_spatial"]
    if not routes:
        faults.append("no rand_proj_spatial decode was recorded")
    faults += [f"{k}: decode was not 'fused'"
               for k in routes if labels(k)["method"] != "fused"]
    faults += [f"supervisor: {line}" for line in log_lines
               if line.startswith("[supervisor]")
               and ("failed" in line or "resumed" in line)]
    return faults


def record_decode_route(estimator: str, method: str) -> None:
    """Count the decode path a spatial estimator resolved to."""
    if not registry.enabled():
        return
    registry.count("kernels", "decode_route", estimator=estimator,
                   method=method)


def record_cg_iters(iters) -> None:
    """Histogram sample of the fused resolvent solve's CG iteration count
    (dropped when ``iters`` is a jit tracer)."""
    if not registry.enabled():
        return
    registry.observe("kernels", "cg_iters", iters)


def record_compile(component: str, name: str, compile_s: float,
                   steady_s: float) -> None:
    """Gauge pair from ``benchmarks.common.timed_with_compile``: first-call
    (trace + lower + compile) vs steady-state seconds for a jitted fn."""
    if not registry.enabled():
        return
    registry.gauge(component, f"{name}.compile_us", compile_s * 1e6)
    registry.gauge(component, f"{name}.steady_us", steady_s * 1e6)
