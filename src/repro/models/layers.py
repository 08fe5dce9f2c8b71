"""Shared layers: norms, RoPE, MLPs, embeddings (pure-functional JAX)."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]


def uniform_init(key, shape, scale, dtype):
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def normal_init(key, shape, std, dtype):
    return (jax.random.normal(key, shape) * std).astype(dtype)


# -- norms --------------------------------------------------------------------

def init_norm(cfg, d: int) -> Params:
    p = {"scale": jnp.ones((d,), cfg.param_dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), cfg.param_dtype)
    return p


def apply_norm(cfg, p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"] + p["bias"]).astype(dt)
    var = (x32**2).mean(-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * p["scale"]).astype(dt)


# -- rotary embeddings ----------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_factor(yarn) -> float:
    """What YaRN multiplies the softmax scale by (1 without it)."""
    return 1.0 if yarn is None else _yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def yarn_rotary_scale(yarn) -> float:
    """What YaRN multiplies cos and sin by (1 without it)."""
    if yarn is None:
        return 1.0
    return _yarn_mscale(yarn.factor, yarn.mscale) / _yarn_mscale(yarn.factor, yarn.mscale_all_dim)


def _yarn_ramp(head_dim: int, theta: float, yarn) -> np.ndarray:
    """0 for the pairs that keep their frequency (more than ``beta_fast``
    rotations over the original context), 1 for those divided by the factor
    (fewer than ``beta_slow``), linear between."""

    def dim(rotations: float) -> float:
        turns = yarn.original_max_position / (rotations * 2 * math.pi)
        return head_dim * math.log(turns) / (2 * math.log(theta))

    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), head_dim - 1)
    if high == low:
        high += 0.001
    return np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)


def rope_frequencies(head_dim: int, theta: float, yarn=None) -> np.ndarray:
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    if yarn is None:
        return freqs
    ramp = _yarn_ramp(head_dim, theta, yarn)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, yarn=None) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).  ``yarn``
    (a ``YarnConfig``) rescales the frequencies and cos/sin."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta, yarn), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None:
        gain = yarn_rotary_scale(yarn)
        cos, sin = cos * gain, sin * gain
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- mlp -------------------------------------------------------------------------

def init_mlp(cfg, key, d: int, f: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    std_in = d**-0.5
    std_out = f**-0.5
    if cfg.mlp == "swiglu":
        return {
            "w_gate": normal_init(k1, (d, f), std_in, cfg.param_dtype),
            "w_up": normal_init(k2, (d, f), std_in, cfg.param_dtype),
            "w_down": normal_init(k3, (f, d), std_out, cfg.param_dtype),
        }
    return {
        "w_in": normal_init(k1, (d, f), std_in, cfg.param_dtype),
        "b_in": jnp.zeros((f,), cfg.param_dtype),
        "w_out": normal_init(k2, (f, d), std_out, cfg.param_dtype),
        "b_out": jnp.zeros((d,), cfg.param_dtype),
    }


def apply_mlp(cfg, p: Params, x: jax.Array) -> jax.Array:
    ct = cfg.compute_dtype
    x = x.astype(ct)
    if cfg.mlp == "swiglu":
        gate = x @ p["w_gate"].astype(ct)
        up = x @ p["w_up"].astype(ct)
        return (jax.nn.silu(gate) * up) @ p["w_down"].astype(ct)
    h = jax.nn.gelu(x @ p["w_in"].astype(ct) + p["b_in"].astype(ct))
    return h @ p["w_out"].astype(ct) + p["b_out"].astype(ct)


# -- embedding / logits -------------------------------------------------------------

def init_embedding(cfg, key) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"embed": normal_init(k1, (cfg.vocab_size, cfg.d_model), 0.02, cfg.param_dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = normal_init(
            k2, (cfg.vocab_size, cfg.d_model), cfg.d_model**-0.5, cfg.param_dtype
        )
    return p


def embed_tokens(cfg, p: Params, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        return p["embed"].astype(cfg.compute_dtype)[tokens]


def logits_matmul(cfg, p: Params, x: jax.Array) -> jax.Array:
    with jax.named_scope("logits"):
        w = p.get("unembed", p["embed"]).astype(cfg.compute_dtype)
        return x @ w.T
