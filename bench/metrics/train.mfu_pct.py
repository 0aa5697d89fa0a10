"""The whole training step's share of the chips' bf16 peak: model FLOPs per
token (``counts.mamba2_train_flops_per_token``) times the tokens the traced
window completed, over the window's host time, chips and peak."""


def read(ctx):
    run = ctx["run"]
    if not run.get("tokens") or "flops_per_token" not in run:
        return None
    rate = run["flops_per_token"] * run["tokens"] / run["seconds"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
