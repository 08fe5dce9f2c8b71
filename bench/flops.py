"""Operations and bytes a step needs, counted from the configuration's shapes.

Counts are of the model's work: matrix products at 2 FLOPs per multiply-add,
attention over the positions each query may see (causal, or inside its
window), and the logits the step returns.  Work a program adds beyond that
(masked blocks it computes anyway, padding, recomputation) is not counted, so
a share of a peak computed from these counts cannot pass 100% for the wrong
reason.  Element-wise work (norms, activations, the SSM's scan) is not counted.
"""

from __future__ import annotations

from typing import Any


def layer_kinds(m: dict[str, Any]) -> list[int]:
    """Attention window of each layer (0 = full attention)."""
    window = m.get("sliding_window", 0)
    glob = set(m.get("global_layers", ()))
    return [0 if (not window or i in glob) else window for i in range(m["num_layers"])]


def matmul_params(m: dict[str, Any]) -> dict[str, int]:
    """Weights that multiply each token, per layer and in the head."""
    d, f, H, KV = m["d_model"], m["d_ff"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // H
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * f
    ssm = 0
    if m.get("ssm"):
        s = m["ssm"]
        din = s["expand"] * d
        nh = din // s["head_dim"]
        ssm = d * (2 * din + 2 * s["d_state"] + nh) + din * d
    layer = attn + mlp + ssm
    head = m["vocab_size"] * d
    return {"layer": layer, "layers": layer * m["num_layers"], "head": head}


def param_count(m: dict[str, Any]) -> int:
    """Every parameter of the model (embedding counted once when tied)."""
    d, H, L = m["d_model"], m["num_heads"], m["num_layers"]
    mp = matmul_params(m)
    per_layer_vec = 2 * d                                   # two norms
    if m.get("ssm"):
        s = m["ssm"]
        din = s["expand"] * d
        nh = din // s["head_dim"]
        conv = din + 2 * s["d_state"]
        per_layer_vec += conv * s["d_conv"] + conv + 3 * nh + din + 2 * d
    emb = m["vocab_size"] * d * (1 if m.get("tie_embeddings") else 2)
    return mp["layers"] + L * per_layer_vec + emb + d


def _attn_flops_per_query(m: dict[str, Any], seen: float) -> float:
    """QK^T and PV for one query over ``seen`` keys, in one layer."""
    H = m["num_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    return 4.0 * H * hd * seen


def prefill_flops(m: dict[str, Any], batch: int, prompt: int) -> float:
    """Prompt of ``prompt`` tokens per row, logits of the last position."""
    mp = matmul_params(m)
    total = 2.0 * mp["layers"] * batch * prompt + 2.0 * mp["head"] * batch
    for w in layer_kinds(m):
        seen = sum(min(i + 1, w) if w else i + 1 for i in range(prompt))
        total += batch * _attn_flops_per_query(m, 1) * seen
    return total


def decode_flops(m: dict[str, Any], batch: int, position: int) -> float:
    """One token per row at ``position`` (0-based), with its logits."""
    mp = matmul_params(m)
    total = 2.0 * (mp["layers"] + mp["head"]) * batch
    for w in layer_kinds(m):
        seen = min(position + 1, w) if w else position + 1
        total += batch * _attn_flops_per_query(m, seen)
    return total


def decode_bytes(m: dict[str, Any], batch: int, position: int, weight_bytes: int = 2,
                 cache_bytes: int = 2) -> float:
    """HBM bytes one decode step needs: every weight once, plus the keys and
    values each row has cached up to and including ``position``."""
    d, H, KV = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // H
    weights = param_count(m) * weight_bytes
    kv = 0.0
    for w in layer_kinds(m):
        seen = min(position + 1, w) if w else position + 1
        kv += batch * seen * 2 * KV * hd * cache_bytes
    return weights + kv


def train_flops_per_token(m: dict[str, Any], seq: int) -> float:
    """Forward and backward (3x forward), without recomputation: 6 per
    multiply weight, plus causal or windowed attention averaged over ``seq``."""
    mp = matmul_params(m)
    total = 6.0 * (mp["layers"] + mp["head"])
    for w in layer_kinds(m):
        seen = sum(min(i + 1, w) if w else i + 1 for i in range(seq)) / seq
        total += 3.0 * _attn_flops_per_query(m, seen)
    return total
