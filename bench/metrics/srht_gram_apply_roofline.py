"""Share of its roofline that the fused ``srht_gram_apply`` kernel reaches:
the least time the chip could take for the calls in the traced window (the
larger of their FLOPs over peak FLOP/s and their bytes over HBM bandwidth,
from ``counts.srht_gram_apply`` at the call's shapes) over the device time
of the ops named ``srht_gram_apply``. At the exchange's shapes the bytes
bound it: a few dozen FLOPs per float moved."""

from bench import counts, trace


def read(ctx):
    shape = ctx["run"].get("srht_gram_apply")
    if shape is None:
        return None
    secs, calls = trace.op_seconds(ctx["trace"], r"srht_gram_apply")
    if calls == 0 or secs <= 0:
        return None
    flops, nbytes = counts.srht_gram_apply(**shape)
    peak = ctx["peak"]
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * calls * least / secs
