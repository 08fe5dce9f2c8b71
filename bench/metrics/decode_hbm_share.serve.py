"""Bytes a decode step needs over what the chip's HBM moves in its device
time (%): every weight once, plus each row's cached keys and values up to
its position, averaged over the positions a batch decodes."""

from bench import flops


def read(ctx):
    t = ctx.reduction.per_call_s("serve_decode")
    if not t:
        return None
    mix = ctx.mix
    B, PL, G = mix["max_batch_size"], mix["prompt_len"], mix["new_tokens"]
    positions = range(PL, PL + G - 1)
    need = sum(flops.decode_bytes(ctx.model, B, p) for p in positions) / len(positions)
    return 100.0 * need / (t * ctx.peaks["hbm_bytes_per_s"])
