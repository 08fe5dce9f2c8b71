"""Model FLOPs of the traced prefill and decode calls over their device time
at the chip's bf16 peak (%), for expert layers that hold a share of their
experts: a token's routed work counts at ``top_k * held / num_experts``
experts (the pairs that land on held experts, on average; 0.75 for 8 of 64
experts at top 6), the shared experts and the router whole.  Otherwise as
``step_mfu.serve``, which does the counting."""

from pathlib import Path
from types import SimpleNamespace

from bench.apps.common import load_module


def read(ctx):
    moe = ctx.model.get("moe")
    if not moe:
        return None
    held = moe.get("num_held") or moe["num_experts"]
    share = dict(moe, top_k=moe["top_k"] * held / moe["num_experts"])
    whole = load_module(Path(__file__).with_name("step_mfu.serve.py"), "bench_metric_step_mfu")
    return whole.read(SimpleNamespace(**{**vars(ctx), "model": dict(ctx.model, moe=share)}))
