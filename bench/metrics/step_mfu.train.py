"""Model FLOPs of a train step (forward and backward, no recomputation
counted; bench/flops.py) over its device time x chips x bf16 peak (%)."""

from bench import flops


def read(ctx):
    t = ctx.reduction.per_call_s("train_step")
    if not t:
        return None
    mix = ctx.mix
    work = flops.train_flops_per_token(ctx.model, mix["seq"]) * mix["batch"] * mix["seq"]
    return 100.0 * work / (t * ctx.chips * ctx.peaks["flops_per_s_bf16"])
