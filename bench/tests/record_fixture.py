"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_fixture.py <out.xplane.pb>

On the chip: inside a ``bench.window`` span, three ``bench.dispatch`` /
``bench.wait`` rounds of one jitted program that calls the fused
``srht_gram_apply`` kernel twice on (4, 256, 1024) operands and a
(512, 512) matmul, then a 50 ms sleep in a ``bench.sleep`` span, so the
trace holds a kernel, a container-free op mix, host spans and a long
idle gap.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp

    from bench import trace
    from repro.kernels.srht_fused import srht_gram_apply_pallas

    if jax.devices()[0].platform != "tpu":
        print("record_fixture: needs the chip", file=sys.stderr)
        return 3

    @jax.jit
    def prog(v, signs, mask, a):
        s = srht_gram_apply_pallas(v, signs, mask, scale=1.0 / 1024)
        s = srht_gram_apply_pallas(s, signs, mask, scale=1.0 / 1024)
        return s.sum() + (a @ a).sum()

    k = jax.random.split(jax.random.key(0), 4)
    v = jax.random.normal(k[0], (256, 1024))
    signs = jax.random.rademacher(k[1], (4, 256, 1024), jnp.float32)
    mask = jax.random.bernoulli(k[2], 0.0625, (4, 256, 1024)).astype(jnp.float32)
    a = jax.random.normal(k[3], (512, 512))
    prog(v, signs, mask, a).block_until_ready()
    logdir = tempfile.mkdtemp()
    with trace.capture(logdir):
        with trace.span("bench.window"):
            for _ in range(3):
                with trace.span("bench.dispatch"):
                    r = prog(v, signs, mask, a)
                with trace.span("bench.wait"):
                    r.block_until_ready()
            with trace.span("bench.sleep"):
                time.sleep(0.05)
    path = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(path, out)
    tr = trace.reduce(out)
    print("fixture", out, os.path.getsize(out), "bytes; devices", sorted(tr.ops),
          "ops", {d: len(o) for d, o in tr.ops.items()}, "busy_s", trace.busy_s(tr),
          "window_s", tr.window_s, "gram", trace.op_seconds(tr, "srht_gram_apply"))
    for plane in __import__("jax").profiler.ProfileData.from_file(out).planes:
        print("plane", plane.name, [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines][:12])
    print("top", trace.top_ops(tr, 5))
    print("gaps", trace.idle_gaps(tr, 5))
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
