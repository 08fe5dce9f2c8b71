"""Model FLOPs of the traced prefill and decode calls over their device time
at the chip's bf16 peak (%).  Decode calls are counted at the mean position
of a batch's decode; every row of the compiled batch counts."""

from bench import flops


def read(ctx):
    red, mix = ctx.reduction, ctx.mix
    pre, dec = red.calls.get("serve_prefill", []), red.calls.get("serve_decode", [])
    if not pre and not dec:
        return None
    B, PL, G = mix["max_batch_size"], mix["prompt_len"], mix["new_tokens"]
    positions = range(PL, PL + G - 1)
    per_dec = sum(flops.decode_flops(ctx.model, B, p) for p in positions) / len(positions)
    work = len(pre) * flops.prefill_flops(ctx.model, B, PL) + len(dec) * per_dec
    return 100.0 * work / ((sum(pre) + sum(dec)) * ctx.peaks["flops_per_s_bf16"] * ctx.chips)
