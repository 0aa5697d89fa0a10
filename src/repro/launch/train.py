"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --preset tiny --steps 200 --estimator rand_proj_spatial --clients 4

- --preset tiny|small|full scales the arch config (tiny/small run on CPU;
  full is the real config for cluster meshes).
- The DME estimator compresses the cross-client gradient mean exactly as in
  the multi-pod deployment (client axis = leading batch dim; on a real mesh
  the axis shards over 'pod').
- Fault tolerance: checkpoints every --ckpt-every steps; restart the same
  command line and it resumes; --inject-failures demonstrates recovery.
  --max-restarts 0 makes the first step failure fatal (a compile error then
  surfaces once instead of recompiling on every retry).
"""
from __future__ import annotations

import argparse
import functools
import os

import jax

from .. import configs
from ..core import codec
from ..data import SyntheticLM
from ..models import init_params
from ..optim import AdamW
from ..train import make_train_step
from ..train.train_step import init_train_state
from ..train.supervisor import FaultPlan, Supervisor
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


def preset_config(arch: str, preset: str):
    cfg = configs.get_config(arch)
    if preset == "full":
        return cfg
    if preset == "tiny":
        return configs.reduce_for_smoke(cfg)
    # "small": ~100M-class model of the same family
    kw = dict(d_model=512, vocab_size=8192, n_blocks=min(cfg.n_blocks, 8),
              vocab_pad_multiple=64, remat="none", dtype="float32")
    if cfg.n_heads:
        kw.update(n_heads=8, n_kv_heads=min(cfg.n_kv_heads, 4), d_head=64)
    if cfg.d_ff:
        kw.update(d_ff=2048)
    if cfg.n_experts:
        kw.update(n_experts=min(cfg.n_experts, 8), d_ff_expert=512)
    if cfg.mamba_d_inner:
        kw.update(mamba_d_inner=1024, d_state=64)
    return cfg.replace(**kw)


def main(argv=None, log_fn=print):
    """Run the CLI; ``log_fn`` receives every step and Supervisor line.
    Returns the Supervisor's [(step, loss), ...] history."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m", choices=list(configs.ARCHS))
    ap.add_argument("--preset", default="tiny", choices=["tiny", "small", "full"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4, help="per-client batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--clients", type=int, default=4, help="DME clients (0 = no compression)")
    ap.add_argument("--estimator", default="rand_proj_spatial")
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--d-block", type=int, default=1024)
    ap.add_argument("--transform", default="avg")
    ap.add_argument("--ef", action="store_true", help="error feedback (top_k/wangni)")
    ap.add_argument("--dme-ownership", type=int, default=0,
                    help="owner shards for the sharded server decode "
                         "(docs/DESIGN.md §10); 0 = replicated decode")
    ap.add_argument("--pipeline-stages", type=int, default=0,
                    help="layer-pipeline the block stack over this many "
                         "devices (GPipe over a 'pipe' mesh axis); 0 = off")
    ap.add_argument("--pipeline-microbatches", type=int, default=0,
                    help="microbatch count for --pipeline-stages "
                         "(default: the stage count)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--non-iid", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10,
                    help="log (and record in the history) every Nth step's loss")
    ap.add_argument("--max-restarts", type=int, default=10,
                    help="step failures the Supervisor restores from before "
                         "it re-raises")
    ap.add_argument("--inject-failures", default="", help="comma steps, e.g. 30,80")
    ap.add_argument("--resize", default="", help="step:new_n, e.g. 100:3")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = preset_config(args.arch, args.preset)
    log_fn(f"[train] {cfg.name} preset={args.preset}: {cfg.n_params()/1e6:.1f}M params, "
          f"{args.clients or 1} clients, estimator="
          f"{args.estimator if args.clients else 'none (uncompressed)'}")
    optimizer = AdamW(lr=args.lr, warmup_steps=20)

    dme = None
    if args.clients:
        dme = codec.build(args.estimator, k=args.k, d_block=args.d_block,
                          transform=args.transform, ef=args.ef)

    pipe_mesh = None
    if args.pipeline_stages:
        pipe_mesh = make_mesh((args.pipeline_stages,), ("pipe",))

    def make_step(n_clients):
        spec = dme
        step = make_train_step(cfg, optimizer, dme_spec=spec if n_clients else None,
                               dme_ownership=args.dme_ownership,
                               mesh=pipe_mesh,
                               pipeline_stages=args.pipeline_stages,
                               pipeline_microbatches=args.pipeline_microbatches)
        return jax.jit(step, donate_argnums=(0, 1))

    def make_data(n_clients):
        data = SyntheticLM(
            vocab_size=cfg.vocab_size, seq_len=args.seq, batch=args.batch,
            n_clients=n_clients, seed=args.seed, non_iid=args.non_iid,
            embed_dim=cfg.d_model if cfg.input_mode == "embeddings" else 0,
        )
        return functools.partial(_data_at, data)

    def _data_at(data, step):
        return data.batch_at(step)

    def init_state():
        params = init_params(cfg, jax.random.key(args.seed))
        return params, init_train_state(cfg, optimizer, params, dme, args.clients)

    plan = FaultPlan(
        fail_at_steps=tuple(int(s) for s in args.inject_failures.split(",") if s),
        resize_at={int(kv.split(":")[0]): int(kv.split(":")[1])
                   for kv in args.resize.split(",") if kv} or None,
    )
    sup = Supervisor(
        make_step=make_step, make_data=make_data, init_state=init_state,
        ckpt_dir=os.path.join(args.ckpt_dir, f"{cfg.name}_{args.preset}"),
        n_clients=args.clients, ckpt_every=args.ckpt_every,
        max_restarts=args.max_restarts,
    )
    params, state, history = sup.run(args.steps, fault_plan=plan,
                                     log_every=args.log_every, log_fn=log_fn)
    if history:
        first, last = history[0][1], history[-1][1]
        log_fn(f"[train] loss {first:.4f} -> {last:.4f} over {args.steps} steps")
    return history


if __name__ == "__main__":
    main()
