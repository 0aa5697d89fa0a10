import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init). Everything below is ordinary code.

# Multi-pod dry-run: .lower().compile() every (arch x input-shape x mesh)
# cell on placeholder host devices; record memory/cost/collective analysis.
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-67b \
#         --shape train_4k --mesh both --out results/dryrun
#
# Cells are cached as JSON (skip if present unless --force): the full 40-cell
# sweep is resumable and composes with benchmarks/roofline.py, which renders
# docs/EXPERIMENTS.md tables from the same JSON.

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from .. import configs
from ..dist import sharding as shard_lib
from ..models import transformer
from ..optim import AdamW
from ..train import make_train_step
from ..core import codec
from . import hlo_stats, specs
from .mesh import make_mesh, make_production_mesh

RESULT_DIR_DEFAULT = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def _cell_fn_and_args(cfg, shape_name, mesh, dme: str, knobs: dict):
    """Build (fn, example_args) for one cell."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models import act_sharding

    kind = specs.SHAPES[shape_name]["kind"]
    # canonical activation layout: batch over the DP axes (§Perf: prevents
    # GSPMD from propagating a batch-replicated layout through the stack).
    if knobs.get("act_constraint", True) and kind != "decode":
        dp = shard_lib.dp_axes(mesh)
        act_sharding.set_constraint(NamedSharding(mesh, P(dp, None, None)))
    else:
        act_sharding.set_constraint(None)
    model_pref = (
        shard_lib.MODEL_PREF_EP if knobs.get("ep_first") else shard_lib.MODEL_PREF
    )
    params = specs.params_specs(
        cfg, mesh, model_pref=model_pref, fsdp=not knobs.get("no_fsdp", False)
    )
    if kind == "train":
        opt = AdamW(lr=3e-4)
        state = {"opt": specs.opt_state_specs(opt, params)}
        if dme == "off":
            step_fn = make_train_step(cfg, opt)
            batch = specs.batch_specs(cfg, shape_name, mesh)
        else:
            client_axes = ("pod", "data") if dme == "poddata" else (dme,)
            n_clients = 1
            for a in client_axes:
                n_clients *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
            spec = codec.build(
                knobs.get("estimator", "rand_proj_spatial"),
                k=knobs.get("k", 64),
                d_block=knobs.get("d_block", 1024),
                transform=knobs.get("transform", "avg"),
                shared_randomness=not knobs.get("per_chunk", False),
                decode_method=knobs.get("decode_method", "gram"),
                use_pallas="never",  # XLA path in the lowered graph off-TPU
            )
            step_fn = make_train_step(
                cfg, opt, dme_spec=spec, mesh=mesh, client_axes=client_axes,
                dme_impl=knobs.get("dme_impl", "auto"),
            )
            batch = specs.batch_specs(cfg, shape_name, mesh, n_clients=n_clients)
        step = jax.ShapeDtypeStruct((), jnp.int32)
        return step_fn, (params, state, batch, step)

    cache, tokens, positions = specs.decode_specs(cfg, shape_name, mesh)
    if kind == "prefill":
        fn = lambda p, c, t: transformer.prefill(p, cfg, c, t)
        return fn, (params, cache, tokens)
    fn = lambda p, c, t, q: transformer.decode_step(p, cfg, c, t, q)
    return fn, (params, cache, tokens, positions)


def run_cell(arch: str, shape_name: str, multi_pod: bool, dme: str, knobs=None) -> dict:
    knobs = knobs or {}
    t0 = time.time()
    cfg = configs.get_config(arch)
    cfg_over = {k: knobs[k] for k in
                ("n_blocks", "force_unroll", "remat", "attn_kv_block", "dtype",
                 "mamba_chunk", "capacity_factor", "mamba_split_proj",
                 "param_dtype", "attn_impl", "gqa_repeat_kv")
                if k in knobs}
    if cfg_over:
        cfg = cfg.replace(**cfg_over)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "dme": dme,
        "knobs": knobs,
        "n_params": cfg.n_params(),
        "n_params_active": cfg.n_params_active(),
    }
    ok, why = specs.supported(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    try:
        if "mesh_shape" in knobs:
            # ablation meshes, e.g. [2, 256, 1] = DP-dominant 2-pod (§Perf H-c.4)
            mesh = make_mesh(tuple(knobs["mesh_shape"]), ("pod", "data", "model"))
            rec["mesh"] = "x".join(str(s) for s in knobs["mesh_shape"])
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        n_devices = mesh.devices.size
        fn, args = _cell_fn_and_args(cfg, shape_name, mesh, dme, knobs)
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

        mem = {}
        try:
            ma = compiled.memory_analysis()
            for field in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            ):
                if hasattr(ma, field):
                    mem[field] = int(getattr(ma, field))
        except Exception as e:  # CPU backend may not support it
            mem["error"] = repr(e)

        cost = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            for key in ("flops", "bytes accessed", "transcendentals", "optimal_seconds"):
                if key in ca:
                    cost[key] = float(ca[key])
        except Exception as e:
            cost["error"] = repr(e)

        text = compiled.as_text()
        coll = hlo_stats.collective_stats(text, default_group=2 if multi_pod else 16)
        rec.update(
            status="ok",
            n_devices=n_devices,
            lower_s=round(t_lower - t0, 2),
            compile_s=round(t_compile - t_lower, 2),
            memory=mem,
            cost=cost,
            collectives=coll,
            hlo_bytes=len(text),
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def cell_path(out_dir, arch, shape_name, mesh_name, dme, tag="") -> str:
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    return os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}__{dme}{suffix}.json")


def run_calibration(arch, shape_name, multi_pod, dme, knobs=None) -> dict:
    """Two-point block-count calibration: compile at n_blocks in {1, 2} with
    all loops unrolled (no HLO whiles -> exact cost_analysis + collective
    parse), then affine-extrapolate f(nb) = a + b*nb to the full depth.
    Needed because XLA cost analysis counts while bodies ONCE (docs/EXPERIMENTS.md
    §Dry-run, methodology)."""
    knobs = dict(knobs or {})
    cfg = configs.get_config(arch)
    points = {}
    for nb in (1, 2):
        k = dict(knobs)
        k.update(n_blocks=nb, force_unroll=True)
        points[nb] = run_cell(arch, shape_name, multi_pod, dme, k)
        if points[nb]["status"] != "ok":
            return {"status": "error", "points": points, "arch": arch,
                    "shape": shape_name, "dme": dme,
                    "mesh": "pod2x16x16" if multi_pod else "pod16x16"}

    def fit(get):
        y1, y2 = get(points[1]), get(points[2])
        b = y2 - y1
        a = y1 - b
        return a, b

    full_nb = cfg.n_blocks
    out = {
        "status": "ok",
        "arch": arch, "shape": shape_name, "dme": dme,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "knobs": knobs, "n_blocks_full": full_nb,
        "points": points,
    }
    for name, get in [
        ("flops", lambda r: r["cost"].get("flops", 0.0)),
        ("bytes", lambda r: r["cost"].get("bytes accessed", 0.0)),
        ("wire_bytes", lambda r: r["collectives"]["totals"]["wire_bytes"]),
        ("coll_result_bytes", lambda r: r["collectives"]["totals"]["result_bytes"]),
    ]:
        a, b = fit(get)
        out[f"{name}_full"] = a + b * full_nb
        out[f"{name}_fit"] = {"a": a, "b": b}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=["all"] + list(specs.SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--dme", default="default",
                    help="off|pod|data|poddata|default (default: pod on multi-pod "
                         "train cells, off elsewhere)")
    ap.add_argument("--out", default=RESULT_DIR_DEFAULT)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for experiment variants")
    ap.add_argument("--knobs", default="{}", help="JSON perf knobs")
    ap.add_argument("--calibrate", action="store_true",
                    help="two-point unrolled cost calibration instead of full compile")
    args = ap.parse_args()

    archs = list(configs.ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(specs.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    knobs = json.loads(args.knobs)

    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                if args.dme == "default":
                    kind = specs.SHAPES[shape_name]["kind"]
                    dme = "pod" if (multi_pod and kind == "train") else "off"
                else:
                    dme = args.dme
                mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
                tag = ("calib" + args.tag) if args.calibrate else args.tag
                path = cell_path(args.out, arch, shape_name, mesh_name, dme, tag)
                if os.path.exists(path) and not args.force:
                    print(f"[skip cached] {path}")
                    continue
                print(f"[run] {arch} x {shape_name} x {mesh_name} dme={dme} "
                      f"{'CALIB' if args.calibrate else ''}...", flush=True)
                if args.calibrate:
                    cfg0 = configs.get_config(arch)
                    ok, why = specs.supported(cfg0, shape_name)
                    if not ok:
                        rec = {"status": "skipped", "reason": why, "arch": arch,
                               "shape": shape_name, "mesh": mesh_name, "dme": dme}
                    else:
                        rec = run_calibration(arch, shape_name, multi_pod, dme, knobs)
                else:
                    rec = run_cell(arch, shape_name, multi_pod, dme, knobs)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                if args.calibrate:
                    extra = f"flops_full={rec.get('flops_full'):.3e}" if status == "ok" else rec.get("reason", "error")
                else:
                    extra = (
                        f"compile={rec.get('compile_s')}s flops={rec.get('cost', {}).get('flops')}"
                        if status == "ok" else rec.get("reason") or rec.get("error")
                    )
                print(f"[{status}] {arch} x {shape_name} x {mesh_name}: {extra}", flush=True)


if __name__ == "__main__":
    main()
