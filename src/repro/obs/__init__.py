"""repro.obs — metrics registry, round-timeline tracing, profiling hooks.

The observability layer for the codec -> collectives -> FL stack
(docs/OBSERVABILITY.md). Everything is OFF by default and the disabled
paths are one-flag-check no-ops, so an uninstrumented run is bitwise
identical to pre-instrumentation behaviour (tests/test_obs.py).

    from repro import obs

    obs.enable()                                  # metrics on
    tracer = obs.install_tracer(obs.Tracer())     # + round timeline
    ...run rounds...
    tracer.write("trace.json")                    # Perfetto-loadable
    print(obs.snapshot()["counters"])             # flat metrics export

Three submodules:

- ``registry`` — counters/gauges/histograms keyed ``component/name``,
  recording ``span``s and zero-duration ``marker``s; jit-tracer-safe.
- ``trace``    — Chrome-trace/Perfetto event collection, one track per
  round phase; ``install_tracer`` makes it the process emission target.
- ``profile``  — ``jax.profiler`` session wiring + kernel dispatch / CG /
  compile-time telemetry hooks.
"""
from .profile import (  # noqa: F401
    main_path_faults,
    profiler_session,
    record_cg_iters,
    record_compile,
    record_decode_route,
    record_dispatch,
)
from .registry import (  # noqa: F401
    count,
    disable,
    enable,
    enabled,
    gauge,
    marker,
    observe,
    reset,
    snapshot,
    span,
    tracer_drops,
)
from .trace import (  # noqa: F401
    PHASES,
    Tracer,
    current_tracer,
    install_tracer,
    now_us,
    uninstall_tracer,
)
