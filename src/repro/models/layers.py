"""Shared layers: norms, RoPE, MLPs, embeddings (pure-functional JAX)."""

from __future__ import annotations

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

def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
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
