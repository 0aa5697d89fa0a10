"""Bring-up smoke run of the main path on a TPU, in one process.

    python chip_smoke.py            # one chip: kernels, DME training, FL rounds
    python chip_smoke.py --chips 4  # four chips: the sharded DME exchange only

One chip runs three phases at published widths:

1. kernels: every fused SRHT kernel (``kernels/fwht.py``,
   ``kernels/srht_fused.py``) compiled and run once at the shapes of a
   mamba2-130m gradient exchange (n = 4 clients, C = 126,075 chunks of
   d = 1024), against its ``kernels/ref.py`` oracle;
2. train: 20 DME training steps of mamba2-130m at full width through
   ``repro.launch.train.main`` (Supervisor -> make_train_step ->
   compressed_mean_tree -> fused rand_proj_spatial decode), 4 clients of
   batch 4 at sequence length 512; then the gradient mean the same step
   decodes at step 0, projected on the exact mean of the clients'
   gradients, which must come back at 1 (the estimator is unbiased);
3. fl: the paper's power-iteration rounds (d = 1024, n = 10, 20 rounds)
   through ``repro.fl.run.main``.

``--chips 4`` runs one DME training step of the same model on a 4-chip
``pod`` mesh, one client per chip, with the owner-sharded decode (shard_map,
``all_to_all`` payload routing), and the same step on one chip. Both are
built with an optimizer that returns the decoded gradient mean in place of
new parameters, and the two means are compared.

Every phase runs even when an earlier one failed; the exit code is non-zero
when any failed, when no TPU is found, or when the package is not next to
this file. Only a run in which every check passed prints, as its last line,
``{"ok": true, "device": {...}}``. Times printed are bring-up readings from
one cold run, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import shutil
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".cache", "chip_smoke")

KERNEL_REL_TOL = 1e-4     # max |kernel - oracle| / max |oracle|, float32
# max |4-chip mean - 1-chip mean| / max |1-chip mean|. On a v5e the clients'
# bfloat16 gradients already differ by about 2% (max-abs and L2) between one
# client per backward pass and four vmapped, and the decode carries that
# through; a misrouted or misdecoded chunk differs by its whole size.
SHARDED_REL_TOL = 0.05
PROJ_TOL = 0.05           # |<decoded, exact>/|exact|^2 - 1| at step 0
ARCH, D_BLOCK, K, CLIENTS = "mamba2-130m", 1024, 64, 4
BATCH, SEQ, STEPS = 4, 512, 20   # per-client batch; twice the SSD chunk


def log(msg: str) -> None:
    print(msg, flush=True)


def fresh_dir(*parts: str) -> str:
    """An empty directory under the checkout's .cache, so nothing a former
    run left there (a checkpoint, a beta bank) is read back."""
    path = os.path.join(WORK, *parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def max_rel_err(got_trees, want_trees):
    """(max |got - want|, max |want|) over matching leaves."""
    import jax
    import jax.numpy as jnp

    err = max(float(jnp.max(jnp.abs(g - w)))
              for g, w in zip(jax.tree.leaves(got_trees), jax.tree.leaves(want_trees)))
    ref = max(float(jnp.max(jnp.abs(w))) for w in jax.tree.leaves(want_trees))
    return err, ref


# ------------------------------------------------------------------- phases


def n_chunks_of(cfg) -> int:
    import jax
    import numpy as np

    from repro.core import chunking
    from repro.models import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    d_flat = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    return chunking.num_chunks(d_flat, D_BLOCK)


def phase_kernels(cfg) -> list[str]:
    """Each fused kernel once at the exchange's real shapes, against its
    oracle; data is drawn on the device and only two scalars come back."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.fwht import fwht_pallas
    from repro.kernels.srht_fused import (
        fwht_rowsigns_pallas,
        srht_decode_sum_pallas,
        srht_gram_apply_pallas,
    )

    n, c, d = CLIENTS, n_chunks_of(cfg), D_BLOCK
    s = 1.0 / math.sqrt(d)
    log(f"[kernels] n={n} C={c} d={d}")

    cases = {
        "fwht": (
            ((c, d),),
            lambda x: fwht_pallas(x),
            lambda x: ref.fwht_ref(x),
        ),
        "fwht_signs": (
            ((c, d), (d,)),
            lambda x, sg: fwht_pallas(x, sg, with_signs=True, scale=s),
            lambda x, sg: ref.fwht_ref(x * sg) * s,
        ),
        "fwht_rowsigns": (
            ((n * c, d), (n * c, d)),
            lambda x, sg: fwht_rowsigns_pallas(x, sg, sign_pre=True, scale=s),
            lambda x, sg: ref.fwht_rowsigns_ref(x, sg, sign_pre=True, scale=s),
        ),
        "srht_decode_sum/per_chunk": (
            ((n, c, d), (n, c, d)),
            lambda u, sg: srht_decode_sum_pallas(u, sg, scale=s),
            lambda u, sg: ref.fwht_rowsigns_ref(u, sg, sign_post=True, scale=s).sum(0),
        ),
        "srht_decode_sum/shared": (
            ((n, c, d), (n, 1, d)),
            lambda u, sg: srht_decode_sum_pallas(u, sg, scale=s),
            lambda u, sg: ref.fwht_rowsigns_ref(u, sg, sign_post=True, scale=s).sum(0),
        ),
        "srht_gram_apply/per_chunk": (
            ((c, d), (n, c, d), (n, c, d)),
            lambda v, sg, m: srht_gram_apply_pallas(v, sg, m, scale=1.0 / d),
            lambda v, sg, m: ref.srht_gram_apply_ref(v, sg, m),
        ),
        "srht_gram_apply/shared": (
            ((c, d), (n, 1, d), (n, 1, d)),
            lambda v, sg, m: srht_gram_apply_pallas(v, sg, m, scale=1.0 / d),
            lambda v, sg, m: ref.srht_gram_apply_ref(v, sg, m),
        ),
    }

    def draw(key, shapes, gram):
        """Gaussian data first; then Rademacher signs; a gram apply's last
        operand is a 0/1 row mask keeping k of d coordinates on average."""
        keys = jax.random.split(key, len(shapes))
        out = [jax.random.normal(keys[0], shapes[0], jnp.float32)]
        for i, shape in enumerate(shapes[1:], 1):
            if gram and i == len(shapes) - 1:
                out.append(jax.random.bernoulli(keys[i], K / d, shape).astype(jnp.float32))
            else:
                out.append(jax.random.rademacher(keys[i], shape, jnp.float32))
        return out

    failures = []
    for i, (name, (shapes, kernel, oracle)) in enumerate(cases.items()):
        gram = name.startswith("srht_gram")

        @jax.jit
        def compare(key, shapes=shapes, kernel=kernel, oracle=oracle, gram=gram):
            args = draw(key, shapes, gram)
            got, want = kernel(*args), oracle(*args)
            return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))

        t0 = time.perf_counter()
        err, scale = (float(v) for v in compare(jax.random.key(i)))
        rel = err / scale
        ok = math.isfinite(rel) and rel <= KERNEL_REL_TOL
        log(f"[kernels] {name}: max_abs_err={err!r} max_abs_ref={scale!r} "
            f"rel={rel!r} tol={KERNEL_REL_TOL} "
            f"({time.perf_counter() - t0:.1f}s incl. compile) "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"kernel {name}: rel error {rel!r} > {KERNEL_REL_TOL}")
    return failures


class Step0Means:
    """The gradient mean the DME train step decodes at step 0, and the exact
    mean of the same clients' gradients, which the uncompressed train step
    computes from the clients' batches pooled into one. Both steps are built
    with ``MeanProbe``, so each returns its mean in place of new parameters.
    ``__init__`` lowers the two steps and starts their compiles in a thread,
    so that they overlap whatever runs next; ``read()`` runs them."""

    def __init__(self, cfg, seed: int):
        import jax

        from repro.core import codec
        from repro.data import SyntheticLM
        from repro.models import init_params
        from repro.train import make_train_step

        self.cfg, self.seed = cfg, seed
        self.data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=SEQ,
                                batch=BATCH, n_clients=CLIENTS, seed=seed)
        dme = codec.build("rand_proj_spatial", k=K, d_block=D_BLOCK, transform="avg")
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(seed)))
        batch = jax.eval_shape(lambda: self.data.batch_at(0))
        probe = MeanProbe()
        lowered = [
            jax.jit(make_train_step(cfg, probe, dme_spec=dme)).lower(
                params, {"opt": {}}, batch, 0),
            jax.jit(make_train_step(cfg, probe)).lower(
                params, {"opt": {}}, jax.eval_shape(self.pooled, batch), 0),
        ]
        self.pool = concurrent.futures.ThreadPoolExecutor(len(lowered))
        self.compiled = [self.pool.submit(low.compile) for low in lowered]

    @staticmethod
    def pooled(batch):
        """(clients, batch, ...) -> (clients * batch, ...): the plain step's
        loss over the pooled tokens is the mean of the clients' losses."""
        import jax

        return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), batch)

    def read(self):
        """(<decoded, exact>, |exact|^2, |decoded|^2) summed over leaves."""
        import jax
        import jax.numpy as jnp

        from repro.models import init_params

        decoded_step, exact_step = (f.result() for f in self.compiled)
        self.pool.shutdown()
        params = init_params(self.cfg, jax.random.key(self.seed))
        batch = self.data.batch_at(0)
        decoded, _, _ = decoded_step(params, {"opt": {}}, batch, 0)
        exact, _, _ = exact_step(params, {"opt": {}}, self.pooled(batch), 0)

        @jax.jit
        def dots(a, b):
            dot = lambda x, y: sum(jnp.vdot(u.astype(jnp.float32), v.astype(jnp.float32))
                                   for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y)))
            return dot(a, b), dot(b, b), dot(a, a)

        return tuple(float(v) for v in dots(decoded, exact))


def phase_train(preset: str = "full", seed: int = 0) -> list[str]:
    """20 DME steps through the training CLI, every step's loss logged; then
    the step-0 decoded gradient mean against the exact mean."""
    import re

    from repro import obs
    from repro.launch import train

    lines: list[str] = []

    def log_line(line: str) -> None:
        lines.append(line)
        log(line)

    cfg = train.preset_config(ARCH, preset)
    t0 = time.perf_counter()
    means = Step0Means(cfg, seed)  # compiles while the training runs
    log(f"[train] step-0 mean probes lowered in {time.perf_counter() - t0:.1f}s")
    obs.reset()
    obs.enable()
    ckpt = fresh_dir("ckpt")
    t0 = time.perf_counter()
    # lr 1e-3: over a 20-step warm-up at this width, 3e-3 and up make the
    # loss climb by step 19 even with the exact (uncompressed) mean
    history = train.main([
        "--arch", ARCH, "--preset", preset, "--steps", str(STEPS),
        "--clients", str(CLIENTS), "--batch", str(BATCH), "--seq", str(SEQ),
        "--estimator", "rand_proj_spatial", "--k", str(K),
        "--d-block", str(D_BLOCK), "--lr", "1e-3", "--log-every", "1",
        "--max-restarts", "0", "--ckpt-every", "0", "--seed", str(seed),
        "--ckpt-dir", ckpt,
    ], log_fn=log_line)
    wall = time.perf_counter() - t0
    counters = obs.snapshot()["counters"]
    obs.disable()

    failures = obs.main_path_faults(counters, lines)
    for key in sorted(counters):
        if key.startswith("kernels/"):
            log(f"[train] counter {key} = {counters[key]}")
    losses = [loss for _, loss in history]
    if len(losses) != STEPS:
        failures.append(f"train: {len(losses)} logged losses, expected {STEPS}")
    if not all(math.isfinite(v) for v in losses):
        failures.append(f"train: non-finite loss in {losses}")
    elif losses and not losses[-1] < losses[0]:
        failures.append(f"train: last loss {losses[-1]!r} not below the "
                        f"step-0 loss {losses[0]!r}")
    secs = [float(m.group(1)) for m in
            (re.search(r"\] loss=\S+ \(([0-9.]+)s\)", ln) for ln in lines) if m]
    if len(secs) >= 2:
        log(f"[train] bring-up reading, not a benchmark metric: first step "
            f"(trace + compile + run) {secs[0]}s, steady step median "
            f"{statistics.median(secs[1:])}s over {len(secs) - 1} steps, "
            f"phase wall {wall:.1f}s")

    # each step's loss is on a new batch, and 20 warm-up steps move it by
    # about as much as the batches differ; what the step must get right is
    # its gradient: the decoded mean is unbiased, and over ~1e5 chunks its
    # projection on the exact mean concentrates at |exact|^2
    t0 = time.perf_counter()
    dot, exact_sq, decoded_sq = means.read()
    ratio = dot / exact_sq
    cos = dot / math.sqrt(exact_sq * decoded_sq)
    rel_sq_err = (decoded_sq - 2 * dot + exact_sq) / exact_sq
    log(f"[train] step-0 decoded mean against the exact mean: "
        f"<decoded, exact>/|exact|^2 = {ratio!r} (must be within "
        f"{PROJ_TOL} of 1), cosine {cos!r}, |decoded - exact|^2/|exact|^2 "
        f"{rel_sq_err!r} ({time.perf_counter() - t0:.1f}s after training)")
    if not abs(ratio - 1) <= PROJ_TOL:
        failures.append(f"train: step-0 <decoded, exact>/|exact|^2 = {ratio!r}, "
                        f"not within {PROJ_TOL} of 1")
    return failures


def phase_fl() -> list[str]:
    """The paper's power-iteration rounds through the FL CLI."""
    from repro import obs
    from repro.fl import run as fl_run

    out = os.path.join(fresh_dir("fl"), "metrics.json")
    t0 = time.perf_counter()
    code = fl_run.main(["--task", "power_iteration", "--estimator",
                        "rand_proj_spatial", "--rounds", "20",
                        "--metrics-json", out])
    wall = time.perf_counter() - t0
    obs.disable()
    with open(out) as f:
        run = json.load(f)["runs"][0]
    mses = [r["mse"] for r in run["rounds"]]
    failures = [] if code == 0 else [f"fl: exit code {code}"]
    if len(mses) != 20 or not all(m is not None and math.isfinite(m) for m in mses):
        failures.append(f"fl: expected 20 finite round MSEs, got {mses}")
    failures += [f"fl: {f}" for f in obs.main_path_faults(run["metrics"]["counters"])]
    log(f"[fl] {len(mses)} rounds, last mse={mses[-1] if mses else None!r}, "
        f"phase wall {wall:.1f}s (bring-up reading)")
    return failures


class MeanProbe:
    """An optimizer whose update hands back the gradient it is given: a train
    step built with it returns, where the new parameters would be, the mean
    its exchange decoded."""

    def init(self, params):
        return {}

    def update(self, grads, state, params):
        return grads, state, {}


def phase_sharded(preset: str = "full") -> list[str]:
    """One owner-sharded DME training step on a 4-chip 'pod' mesh, one client
    per chip, against the same step on one chip: the compiled mesh step must
    route payloads with an all-to-all, and the two decoded means must agree."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import codec
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import preset_config
    from repro.models import init_params
    from repro.train import make_train_step
    from repro.train.train_step import init_train_state

    n_dev = len(jax.devices())
    if n_dev != CLIENTS:
        return [f"sharded: needs {CLIENTS} chips, found {n_dev}"]
    mesh = make_mesh((CLIENTS,), ("pod",))
    cfg = preset_config(ARCH, preset)
    dme = codec.build("rand_proj_spatial", k=K, d_block=D_BLOCK, transform="avg")
    probe = MeanProbe()
    failures = []

    params = init_params(cfg, jax.random.key(0))
    state = init_train_state(cfg, probe, params, dme, CLIENTS)
    batch = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=SEQ, batch=BATCH,
                        n_clients=CLIENTS).batch_at(0)
    args_1 = jax.device_put((params, state, batch), jax.devices()[0])
    args_4 = (*jax.device_put((params, state), NamedSharding(mesh, P())),
              jax.device_put(batch, NamedSharding(mesh, P("pod"))))
    del params, state, batch
    step_4 = jax.jit(make_train_step(cfg, probe, dme_spec=dme, mesh=mesh,
                                     dme_ownership=CLIENTS))
    step_1 = jax.jit(make_train_step(cfg, probe, dme_spec=dme))

    # trace in turn, then compile both at once: XLA compiles outside the GIL,
    # and the compiles are most of this phase's time
    t0 = time.perf_counter()
    lowered = [step_4.lower(*args_4, 0), step_1.lower(*args_1, 0)]
    with concurrent.futures.ThreadPoolExecutor(len(lowered)) as pool:
        step_4, step_1 = pool.map(lambda low: low.compile(), lowered)
    log(f"[sharded] mesh step and one-chip step compiled in "
        f"{time.perf_counter() - t0:.1f}s (bring-up reading)")
    if "all-to-all" not in step_4.as_text():
        failures.append("sharded: the mesh step's HLO holds no all-to-all")

    t0 = time.perf_counter()
    mean_4, _, metrics_4 = step_4(*args_4, 0)
    loss_4 = float(metrics_4["loss"])
    log(f"[sharded] mesh step: loss={loss_4!r} "
        f"({time.perf_counter() - t0:.1f}s; bring-up reading)")
    del args_4
    mean_4 = jax.device_put(mean_4, jax.devices()[0])
    mean_1, _, metrics_1 = step_1(*args_1, 0)
    loss_1 = float(metrics_1["loss"])
    del args_1
    err, scale = max_rel_err(mean_4, mean_1)
    rel = err / scale
    log(f"[sharded] decoded mean, 4 chips vs 1: max_abs_diff={err!r} "
        f"max_abs_mean={scale!r} rel={rel!r} tol={SHARDED_REL_TOL}; "
        f"loss {loss_4!r} vs {loss_1!r}")
    if not (math.isfinite(rel) and rel <= SHARDED_REL_TOL):
        failures.append(f"sharded: decoded mean rel diff {rel!r} > {SHARDED_REL_TOL}")
    if not (math.isfinite(loss_4) and math.isfinite(loss_1)):
        failures.append(f"sharded: non-finite loss {loss_4!r} / {loss_1!r}")
    return failures


# --------------------------------------------------------------------- main


def run_phase(name, fn, *args) -> list[str]:
    log(f"== phase {name}")
    t0 = time.perf_counter()
    try:
        failures = fn(*args)
    except Exception:  # noqa: BLE001 — report the phase and run the next one
        traceback.print_exc()
        failures = [f"{name}: raised (traceback above)"]
    log(f"== phase {name}: {'ok' if not failures else 'FAILED'} "
        f"({time.perf_counter() - t0:.1f}s)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no repro package under {ROOT}/src", file=sys.stderr)
        return 2
    os.environ["REPRO_BETA_CACHE"] = fresh_dir("beta")  # read at import
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    log(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache}")

    if args.chips == 4:
        failures = run_phase("sharded", phase_sharded)
    else:
        from repro.launch.train import preset_config

        cfg = preset_config(ARCH, "full")
        failures = run_phase("kernels", phase_kernels, cfg)
        failures += run_phase("train", phase_train)
        failures += run_phase("fl", phase_fl)
    shutil.rmtree(os.path.join(WORK, "ckpt"), ignore_errors=True)
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
