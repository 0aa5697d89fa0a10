"""Training cells: the program's jitted DME train step, driven closed-loop.

Set-up builds one compiled step (``repro.train.make_train_step`` with
``AdamW`` and the mix's estimator, jitted with the parameters and state
donated, as ``repro.launch.train`` does), the weights and optimizer state
from the seed, and runs the first three steps through that same object on
batches 0..2. Those steps compile and warm the step and are what the
correctness check compares: each step's loss, each step's decoded mean's
global norm before the clip (the step's ``grad_norm``), the per-leaf norm of
the first gradient as AdamW received it (its first moment after step 1 over
1 - b1) and the per-leaf norm of the parameters' change over the three
steps. The clip and Adam's m/sqrt(v) hide the decoded mean's scale from the
last two; the pre-clip norm shows it.

The window continues the same object from step 3 on fresh batches, with
two steps queued ahead of the one the host waits on (so that a stall of
the shared host shorter than a step leaves the device busy), until
``seconds`` have passed; the steps still queued are waited for and
counted.
``train_tokens_per_s`` is every token of every step over the window's
wall time.

The check recomputes the three steps with ``bench.reference`` from the same
seed and compares (see ``compare``). ``reference_readings`` also gives the
readings of the controls (fp8 matmuls; a bfloat16 exchange) and of the
half-batch fault.
"""
from __future__ import annotations

import functools
import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, counts, gen
from bench.reference import exchange, mamba2, optim
from bench.trace import span

FIRST_STEPS = 3
IN_FLIGHT = 2        # steps queued ahead of the one the host waits on
EXCHANGE_SEED = 0    # the program's step key is fold_in(key(0), offset + step)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def program_config(model: dict):
    """The program's ModelConfig with every size taken from the config file."""
    from repro.configs import get_config

    return get_config(model["arch"]).replace(
        d_model=model["d_model"], n_blocks=model["n_layer"],
        vocab_size=model["vocab_size"],
        vocab_pad_multiple=model["pad_vocab_size_multiple"],
        d_state=model["d_state"], d_conv=model["d_conv"],
        mamba_d_inner=model["expand"] * model["d_model"],
        mamba_headdim=model["headdim"], mamba_ngroups=model["ngroups"],
        mamba_chunk=model["chunk_size"], norm_eps=model["norm_epsilon"],
        dtype=model["dtype"], param_dtype="float32", remat=model["remat"],
        tie_embeddings=True,
    )


def leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).reshape(-1))
                      for x in jax.tree.leaves(tree)])


class Cell:
    def __init__(self, spec: common.CellSpec, seed: int, devices):
        self.spec, self.seed, self.devices = spec, seed, devices
        self.model = gen.model_of(spec.config)
        self.train = spec.config["train"]
        self.mix = spec.mix
        self.n = self.train["clients"]
        self.tokens_per_step = self.n * self.train["batch"] * self.train["seq"]
        self.wkey = jax.random.key(common.sub_seed(seed, "weights"))
        self.dkey = jax.random.key(common.sub_seed(seed, "data"))
        self.offset = common.sub_seed(seed, "exchange", bits=30)

    def leaf_names(self) -> list:
        """The weight tree's leaves, named in the order of the leaf norms."""
        tree = jax.eval_shape(lambda: gen.weights(self.model, self.wkey))
        return [f"{jax.tree_util.keystr(p)}{tuple(x.shape)}"
                for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]

    def batch_fn(self):
        return functools.partial(gen.token_batch, clients=self.n, batch=self.train["batch"],
                                 seq=self.train["seq"], vocab=self.model["vocab_size"])

    # ------------------------------------------------------------ set-up
    def setup(self, built=None):
        """``built``: another seed's ``self.built``, so that a study of many
        seeds in one process traces and compiles the step once."""
        self.built = built or self.build()
        (self.step, self.weights, self.batch, self.init_state, self.norms,
         self.delta, self.program_bytes) = self.built
        self.start()

    def build(self):
        from repro.core import codec
        from repro.models import transformer
        from repro.optim import AdamW
        from repro.train import make_train_step
        from repro.train.train_step import init_train_state

        cfg = program_config(self.model)
        want = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.key(0)))
        got = jax.eval_shape(lambda: gen.weights(self.model, self.wkey))
        if jax.tree.map(lambda a: a.shape, want) != jax.tree.map(lambda a: a.shape, got):
            raise ValueError("the benchmark's weight layout differs from the program's")
        dme = codec.build(self.mix["estimator"], **common.codec_kwargs(self.mix))
        opt = AdamW(**self.train["optimizer"])
        step = jax.jit(make_train_step(cfg, opt, dme_spec=dme, seed=EXCHANGE_SEED),
                       donate_argnums=(0, 1))
        weights = jax.jit(functools.partial(gen.weights, self.model))
        batch = jax.jit(self.batch_fn())
        init_state = jax.jit(lambda p: init_train_state(cfg, opt, p, dme, self.n))
        params = weights(self.wkey)
        args = (params, init_state(params), batch(self.dkey, 0))
        del params
        t0 = time.perf_counter()
        compiled = step.lower(*args, self.offset).compile()
        del args
        log(f"[setup] step lowered and compiled in {time.perf_counter() - t0:.1f}s")
        mem = compiled.memory_analysis()
        program_bytes = 0
        if mem is not None:
            # per device: arguments, outputs not aliased to a donated argument,
            # temporaries and code; memory_stats() leaves the temporaries out
            program_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                             - mem.alias_size_in_bytes + mem.temp_size_in_bytes
                             + mem.generated_code_size_in_bytes)
            log(f"[setup] compiled step memory_analysis: temp={mem.temp_size_in_bytes} "
                f"argument={mem.argument_size_in_bytes} output={mem.output_size_in_bytes} "
                f"alias={mem.alias_size_in_bytes} generated_code={mem.generated_code_size_in_bytes}"
                f" -> {program_bytes} bytes per device")
        norms = jax.jit(leaf_norms)
        delta = jax.jit(lambda p, k: leaf_norms(
            jax.tree.map(jnp.subtract, p, gen.weights(self.model, k))))
        return compiled, weights, batch, init_state, norms, delta, program_bytes

    def start(self):
        """Weights and state from the seed, then the first steps."""
        t0 = time.perf_counter()
        params = self.weights(self.wkey)
        state = self.init_state(params)
        batch = self.batch(self.dkey, 0)
        losses, grad_norms = [], []
        for i in range(FIRST_STEPS):
            if i:
                batch = self.batch(self.dkey, i)
            params, state, out = self.step(params, state, batch, self.offset + i)
            losses.append(out["loss"])
            grad_norms.append(out["grad_norm"])
            if i == 0:
                self.mu_norms = self.norms(state["opt"]["mu"])
        self.delta_norms = self.delta(params, self.wkey)
        jax.block_until_ready((params, state, self.delta_norms))
        self.params, self.state = params, state
        log(f"[setup] weights, state and the first {FIRST_STEPS} steps in "
            f"{time.perf_counter() - t0:.1f}s")
        self.program_readings = (
            np.array([float(x) for x in losses]),
            np.asarray(self.mu_norms) / (1.0 - self.train["optimizer"]["b1"]),
            np.asarray(self.delta_norms),
            np.array([float(x) for x in grad_norms]))

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> dict:
        params, state = self.params, self.state
        i, losses = FIRST_STEPS, []
        t0 = time.perf_counter()
        with span("bench.window"):
            while True:
                with span("bench.batch"):
                    batch = self.batch(self.dkey, i)
                with span("bench.dispatch"):
                    params, state, out = self.step(params, state, batch, self.offset + i)
                i += 1
                losses.append(out["loss"])
                if len(losses) > IN_FLIGHT:
                    with span("bench.wait"):
                        losses[-1 - IN_FLIGHT].block_until_ready()
                    if time.perf_counter() - t0 >= seconds:
                        with span("bench.wait"):
                            jax.block_until_ready(losses[-IN_FLIGHT:])
                        break
        elapsed = time.perf_counter() - t0
        self.params, self.state = params, state
        vals = np.array([float(x) for x in losses])
        steps = len(losses)
        run = {"tokens": steps * self.tokens_per_step, "seconds": elapsed, "steps": steps,
               "flops_per_token": counts.mamba2_train_flops_per_token(self.model)}
        if self.mix["estimator"] == "rand_proj_spatial":
            c = exchange.to_chunks_count(counts.n_params(self.model), self.mix["d_block"])
            run["srht_gram_apply"] = dict(
                n=self.n, c=c, d=self.mix["d_block"],
                c_draw=1 if self.mix["shared_randomness"] else c)
        log(f"[window] {steps} steps in {elapsed:.3f}s, losses {vals.min():.4f}..{vals.max():.4f}")
        return {"metrics": {"train_tokens_per_s": run["tokens"] / elapsed},
                "attempted": steps, "failed": int(np.sum(~np.isfinite(vals))), "run": run}

    def release(self):
        self.params = self.state = None
        gc.collect()

    # ------------------------------------------------------------- check
    def reference_readings(self, mm=None, lowp=None, clients=None):
        """(losses, first-gradient leaf norms, change leaf norms, decoded
        means' pre-clip norms) of the first steps recomputed by the
        reference. ``mm`` rounds the model's matmul operands and ``lowp``
        every array of the exchange (the controls); ``clients`` keeps only
        the first that many clients (the half-batch fault)."""
        n = clients or self.n
        opt_cfg = self.train["optimizer"]
        lg = mamba2.loss_and_grad(self.model, mm)
        batch_at = jax.jit(self.batch_fn())
        params = jax.jit(functools.partial(gen.weights, self.model))(self.wkey)
        init = params
        ostate = optim.init(params)
        update = jax.jit(functools.partial(optim.update, opt_cfg))
        norms = jax.jit(leaf_norms)
        global_norm = jax.jit(optim.global_norm)
        chunk = jax.jit(functools.partial(exchange.to_chunks, d=self.mix["d_block"]))
        unchunk = jax.jit(exchange.from_chunks)
        losses, mean_norms, g_norms = [], [], None
        for t in range(FIRST_STEPS):
            batch = batch_at(self.dkey, t)
            ls, chunks = [], []
            for c in range(n):
                l, g = lg(params, jax.tree.map(lambda x: x[c], batch))
                ls.append(float(l))
                chunks.append(chunk(g))
                del g
            xs = jnp.stack(chunks)
            del chunks
            key = jax.random.fold_in(jax.random.key(EXCHANGE_SEED), self.offset + t)
            mean = unchunk(exchange.compressed_mean(self.mix, key, xs, lowp=lowp), params)
            del xs
            mean_norms.append(float(global_norm(mean)))
            params, ostate = update(mean, ostate, params)
            losses.append(float(np.mean(ls)))
            if t == 0:
                g_norms = np.asarray(norms(ostate["mu"])) / (1.0 - opt_cfg["b1"])
        d_norms = np.asarray(norms(jax.tree.map(jnp.subtract, params, init)))
        return np.array(losses), g_norms, d_norms, np.array(mean_norms)

    def check(self) -> list:
        ref = self.reference_readings()
        return compare(self.program_readings, ref)


def leaf_gaps(got, want, keep=None):
    """Per leaf: | |got| - |want| | / max(|want|, median |want|)."""
    keep = np.ones(len(want), bool) if keep is None else keep
    return np.abs(got - want) / np.maximum(want, np.median(want[keep]))


def worst_leaf(got, want, keep=None):
    keep = np.ones(len(want), bool) if keep is None else keep
    return float(np.max(leaf_gaps(got, want, keep)[keep]))


def compare(got, ref) -> list:
    """[(name, value)] of the numbers a cell may compare (its limits file
    says which it does). Leaves whose reference gradient is under 1e-3 of
    the median leaf's are left out of the change: Adam moves them by
    round-off alone. ``grad_gap_median`` is the median leaf's gap, for a
    cell whose worst leaf is one small leaf's noise. ``mean_norm_gap`` is
    the worst step's gap of the decoded mean's pre-clip global norm: the
    one number that sees the mean's scale (a wrong beta or 1/n)."""
    g_loss, g_grad, g_delta, g_norm = got
    r_loss, r_grad, r_delta, r_norm = ref
    moving = r_grad >= 1e-3 * np.median(r_grad)
    return [
        ("loss_gap", float(np.max(np.abs(g_loss - r_loss) / np.abs(r_loss)))),
        ("mean_norm_gap", float(np.max(np.abs(g_norm - r_norm) / r_norm))),
        ("grad_gap", worst_leaf(g_grad, r_grad)),
        ("grad_gap_median", float(np.median(leaf_gaps(g_grad, r_grad)))),
        ("update_gap", worst_leaf(g_delta, r_delta, moving)),
    ]
