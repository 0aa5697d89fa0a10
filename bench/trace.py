"""Profiler capture, and its reduction to device busy time, idle gaps,
per-op device time and host spans.

The harness brackets its own phases with ``span(name)``
(``jax.profiler.TraceAnnotation``, names ``bench.*``), so the host's spans
and the device's operations land in one trace on one clock. ``reduce``
reads the newest ``*.xplane.pb`` under a log directory:

- device operations: on a TPU the events of each ``/device:TPU:<i>``
  plane's ``XLA Ops`` line, named by their HLO instruction (the op's name
  is its first word); on the CPU backend (rehearsal only) the events
  of the host threads that carry an ``hlo_op`` stat. An op that encloses
  another on its line (a ``while``, a ``call``) counts toward busy time
  but not toward the per-op list, which holds the ops that do the work;
- host spans: events named ``bench.*``; ``bench.window`` bounds the window.

Busy time is the union of the op intervals inside the window, per device,
averaged over devices. An idle gap is a stretch of the window in which no
op ran on a device; it is named by the innermost ``bench.*`` span that
covers its midpoint (``host`` when none does). In a trace recorded on a
v5e the device's timestamps ran about 1.4 ms behind the host's spans
(``tests/data/tpu_small.xplane.pb``), so gaps shorter than a few
milliseconds may carry the name of the span before.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def capture(logdir: str):
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Op:
    start: float      # ns
    end: float
    name: str         # the HLO op's name, e.g. "fusion.12"
    text: str         # the HLO instruction where the trace gives it


@dataclasses.dataclass
class Trace:
    ops: dict             # device id -> [Op] sorted by start, within the window
    spans: list           # [(start, end, name)] of bench.* host spans
    window: tuple         # (start, end) ns of bench.window
    platform: str         # "tpu" or "cpu"

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - stats of an unknown type
        return {}


def op_name(instruction: str) -> str:
    """``%fusion.1 = f32[8] fusion(...)`` -> ``fusion.1``: a TPU trace names
    each op by its whole HLO instruction, operands included."""
    m = re.match(r"%?([\w.\-]+)", instruction)
    return m.group(1) if m else instruction


def latest_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def reduce(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, raw = [], {}
    platform = "cpu"
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            platform = "tpu"
            for line in plane.lines:
                if line.name == "XLA Ops":
                    raw.setdefault(int(m.group(1)), []).extend(
                        Op(ev.start_ns, ev.end_ns, op_name(ev.name), ev.name)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
                    elif plane.name == "/host:CPU":
                        st = _stats(ev)
                        if "hlo_op" in st:
                            raw.setdefault(-1, []).append(
                                Op(ev.start_ns, ev.end_ns, str(st["hlo_op"]), ev.name))
    if platform == "tpu":
        raw.pop(-1, None)
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    window = windows[-1]

    def inside(evs):
        return sorted((o for o in evs if o.end > window[0] and o.start < window[1]),
                      key=lambda o: (o.start, -o.end))

    return Trace(ops={d: inside(e) for d, e in raw.items()},
                 spans=sorted(spans), window=window, platform=platform)


def _busy_intervals(ops, window):
    """Union of op intervals clipped to the window, as sorted disjoint pairs."""
    out = []
    for o in ops:
        s, e = max(o.start, window[0]), min(o.end, window[1])
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran, averaged over the devices in the trace."""
    if not trace.ops:
        return 0.0
    tot = sum(sum(e - s for s, e in _busy_intervals(ops, trace.window))
              for ops in trace.ops.values())
    return tot * 1e-9 / len(trace.ops)


def leaf_ops(ops):
    """Ops that enclose no other op of their line (drops while/call shells)."""
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start >= o.start and nxt.end <= o.end and nxt is not o:
            continue
        out.append(o)
    return out


def op_seconds(trace: Trace, pattern: str) -> tuple[float, int]:
    """(summed device seconds, calls) of leaf ops whose name (not their
    operands) matches the regular expression ``pattern``, over all devices."""
    rx = re.compile(pattern)
    secs, calls = 0.0, 0
    for ops in trace.ops.values():
        for o in leaf_ops(ops):
            if rx.search(o.name):
                secs += (o.end - o.start) * 1e-9
                calls += 1
    return secs, calls


def top_ops(trace: Trace, n: int = 10):
    """[[instruction, seconds]] of the leaf ops that took most device time,
    summed over calls and averaged over devices."""
    acc: dict = {}
    for ops in trace.ops.values():
        for o in leaf_ops(ops):
            key = (o.text or o.name)[:200]
            acc[key] = acc.get(key, 0.0) + (o.end - o.start) * 1e-9
    ndev = max(len(trace.ops), 1)
    return [[k, v / ndev] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10):
    """[[host span, seconds]] of the longest idle stretches of the window."""
    gaps = []
    for ops in trace.ops.values():
        t = trace.window[0]
        for s, e in _busy_intervals(ops, trace.window) + [[trace.window[1]] * 2]:
            if s > t:
                gaps.append((s - t, t, s))
            t = max(t, e)
    gaps.sort(reverse=True)
    out = []
    for dur, s, e in gaps[:n]:
        mid = (s + e) / 2
        inner = [sp for sp in trace.spans
                 if sp[0] <= mid <= sp[1] and sp[2] != WINDOW]
        name = min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner else "host"
        out.append([name, dur * 1e-9])
    return out
