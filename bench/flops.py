"""Operations and bytes a step needs, counted from the configuration's shapes.

Counts are of the model's work: matrix products at 2 FLOPs per multiply-add,
attention over the positions each query may see (causal, or inside its
window), and the logits the step returns.  Work a program adds beyond that
(masked blocks it computes anyway, padding, recomputation, experts it
computes for tokens not routed to them) is not counted, so a share of a peak
computed from these counts cannot pass 100% for the wrong reason.
Element-wise work (norms, activations, the SSM's scan, the router's softmax)
is not counted.

Blocks by kind.  Attention is grouped-query (GQA) or latent (MLA, a
``model["mla"]`` section; full-rank queries only).  MLA is counted in the
form that needs fewer operations in each phase:

- a prefill into an empty cache (and training) in the expanded form: per
  token-key pair ``2*H*(nope+rope)`` for the scores and ``2*H*v`` for the
  values, plus the up-projection of each token's latent ``c`` to its keys
  and values (``r*H*(nope+v)`` weights);
- a decode step in the absorbed form: per token-key pair ``2*H*(r+rope)``
  and ``2*H*r``, against the latent cache, plus each token's absorption
  products (the same ``r*H*(nope+v)`` weights), which is fewer than
  up-projecting every cached position.

The MLP of layer ``i`` is dense at ``d_ff`` for ``i < moe.first_dense``
(every layer without a ``model["moe"]`` section) and routed after that: a
token passes ``top_k`` routed experts, the ``num_shared`` shared experts and
the router.  A decode step reads every weight outside the routed experts
once, the routed experts that the step read (``experts_read`` per routed
layer, as the program counted it), and per cached position ``r + rope``
values a layer for MLA or ``2 * KV * head_dim`` for GQA.
"""

from __future__ import annotations

from typing import Any


def layer_kinds(m: dict[str, Any]) -> list[int]:
    """Attention window of each layer (0 = full attention)."""
    window = m.get("sliding_window", 0)
    glob = set(m.get("global_layers", ()))
    return [0 if (not window or i in glob) else window for i in range(m["num_layers"])]


def _mla(m: dict[str, Any]) -> tuple[int, int, int, int] | None:
    """``(r, rope, nope, v)`` of a latent attention, or None for GQA."""
    a = m.get("mla")
    if not a:
        return None
    if a.get("q_lora_rank"):
        raise ValueError("a compressed query projection (q_lora_rank) is not counted")
    return a["kv_lora_rank"], a["qk_rope_dim"], a["qk_nope_dim"], a["v_head_dim"]


def _routed(m: dict[str, Any], layer: int) -> bool:
    moe = m.get("moe")
    return bool(moe) and layer >= moe.get("first_dense", 0)


def _expert(m: dict[str, Any]) -> int:
    """Weights of one SwiGLU expert."""
    return 3 * m["d_model"] * m["moe"]["expert_d_ff"]


def attn_params(m: dict[str, Any]) -> int:
    """Weights of one layer's attention; each multiplies every token."""
    d, H = m["d_model"], m["num_heads"]
    mla = _mla(m)
    if mla:
        r, rope, nope, v = mla
        return d * H * (nope + rope) + d * (r + rope) + r * H * (nope + v) + H * v * d
    KV = m["num_kv_heads"]
    hd = m.get("head_dim") or d // H
    return d * H * hd + 2 * d * KV * hd + H * hd * d


def layer_params(m: dict[str, Any], layer: int) -> tuple[int, int]:
    """Matrix weights of layer ``layer``: those that multiply each token, and
    all that it stores (they differ in a routed layer)."""
    d = m["d_model"]
    attn = attn_params(m)
    ssm = 0
    if m.get("ssm"):
        s = m["ssm"]
        din = s["expand"] * d
        nh = din // s["head_dim"]
        ssm = d * (2 * din + 2 * s["d_state"] + nh) + din * d
    if _routed(m, layer):
        moe = m["moe"]
        router = d * moe["num_experts"]
        shared = moe.get("num_shared", 0) * _expert(m) + router
        return (attn + moe["top_k"] * _expert(m) + shared + ssm,
                attn + moe["num_experts"] * _expert(m) + shared + ssm)
    mlp = 3 * d * m["d_ff"]
    return attn + mlp + ssm, attn + mlp + ssm


def matmul_params(m: dict[str, Any]) -> dict[str, int]:
    """Weights that multiply each token: in the last layer (every layer, when
    all are alike), summed over the layers, and in the head."""
    per = [layer_params(m, i)[0] for i in range(m["num_layers"])]
    return {"layer": per[-1], "layers": sum(per), "head": m["vocab_size"] * m["d_model"]}


def param_count(m: dict[str, Any]) -> int:
    """Every parameter of the model (embedding counted once when tied)."""
    d, L = m["d_model"], m["num_layers"]
    stored = sum(layer_params(m, i)[1] for i in range(L))
    per_layer_vec = 2 * d                                   # two norms
    if m.get("ssm"):
        s = m["ssm"]
        din = s["expand"] * d
        nh = din // s["head_dim"]
        conv = din + 2 * s["d_state"]
        per_layer_vec += conv * s["d_conv"] + conv + 3 * nh + din + 2 * d
    emb = m["vocab_size"] * d * (1 if m.get("tie_embeddings") else 2)
    return stored + L * per_layer_vec + emb + d


def _attn_flops_per_pair(m: dict[str, Any], decode: bool = False) -> float:
    """Scores and weighted values of one query against one key, in one
    layer: expanded, or absorbed (``decode``) for MLA."""
    H = m["num_heads"]
    mla = _mla(m)
    if mla:
        r, rope, nope, v = mla
        if decode:
            return 2.0 * H * (r + rope) + 2.0 * H * r
        return 2.0 * H * (nope + rope) + 2.0 * H * v
    hd = m.get("head_dim") or m["d_model"] // H
    return 4.0 * H * hd


def prefill_flops(m: dict[str, Any], batch: int, prompt: int) -> float:
    """Prompt of ``prompt`` tokens per row, logits of the last position."""
    mp = matmul_params(m)
    total = 2.0 * mp["layers"] * batch * prompt + 2.0 * mp["head"] * batch
    for w in layer_kinds(m):
        seen = sum(min(i + 1, w) if w else i + 1 for i in range(prompt))
        total += batch * _attn_flops_per_pair(m) * seen
    return total


def decode_flops(m: dict[str, Any], batch: int, position: int) -> float:
    """One token per row at ``position`` (0-based), with its logits."""
    mp = matmul_params(m)
    total = 2.0 * (mp["layers"] + mp["head"]) * batch
    for w in layer_kinds(m):
        seen = min(position + 1, w) if w else position + 1
        total += batch * (_attn_flops_per_pair(m, decode=True) * seen)
    return total


def decode_bytes(m: dict[str, Any], batch: int, position: int, weight_bytes: int = 2,
                 cache_bytes: int = 2, *, experts_read: float | None = None) -> float:
    """HBM bytes one decode step needs: every weight outside the routed
    experts once, ``experts_read`` routed experts in each routed layer
    (required for a model with experts: what the program counted), plus the
    cache each row holds up to and including ``position``."""
    d, H = m["d_model"], m["num_heads"]
    n_params = param_count(m)
    if m.get("moe"):
        if experts_read is None:
            raise ValueError("a model with routed experts needs experts_read")
        routed = sum(_routed(m, i) for i in range(m["num_layers"]))
        n_params += routed * (experts_read - m["moe"]["num_experts"]) * _expert(m)
    weights = n_params * weight_bytes
    mla = _mla(m)
    if mla:
        per_position = mla[0] + mla[1]                      # latent c and rotary key
    else:
        per_position = 2 * m["num_kv_heads"] * (m.get("head_dim") or d // H)
    kv = 0.0
    for w in layer_kinds(m):
        seen = min(position + 1, w) if w else position + 1
        kv += batch * seen * per_position * cache_bytes
    return weights + kv


def train_flops_per_token(m: dict[str, Any], seq: int) -> float:
    """Forward and backward (3x forward), without recomputation: 6 per
    multiply weight, plus causal or windowed attention averaged over ``seq``."""
    mp = matmul_params(m)
    total = 6.0 * (mp["layers"] + mp["head"])
    for w in layer_kinds(m):
        seen = sum(min(i + 1, w) if w else i + 1 for i in range(seq)) / seq
        total += 3.0 * (_attn_flops_per_pair(m) * seen)
    return total
