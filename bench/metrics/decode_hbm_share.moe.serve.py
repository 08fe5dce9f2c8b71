"""Bytes a decode step of a model with routed experts needs over what the
chip's HBM moves in its device time (%): every weight outside the routed
experts once, every expert that a routed layer holds, and each row's cache
up to its position, averaged over the positions a batch decodes.

Every held expert counts as read.  The grouped expert loop reads the
weights of each held expert that a step routes a row to, and at 64 rows a
step, each taking 6 of 64 experts, a held expert goes unrouted with
probability about (58/64)^64, 0.2%."""

from bench import flops


def read(ctx):
    moe = ctx.model.get("moe")
    t = ctx.reduction.per_call_s("serve_decode")
    if not t or not moe:
        return None
    held = moe.get("num_held") or moe["num_experts"]
    mix = ctx.mix
    B, PL, G = mix["max_batch_size"], mix["prompt_len"], mix["new_tokens"]
    positions = range(PL, PL + G - 1)
    need = sum(flops.decode_bytes(ctx.model, B, p, experts_read=held)
               for p in positions) / len(positions)
    return 100.0 * need / (t * ctx.peaks["hbm_bytes_per_s"])
