"""Plain float32 forward pass of a dense GQA transformer with SwiGLU MLPs.

Written from the architecture's description (pre-norm RMSNorm, rotary
embeddings on the full head in rotate-half form, grouped-query attention,
SwiGLU, tied or untied embeddings), not from the program's code, and run one
layer at a time so that a 32-layer model fits beside its activations.  The
weights come from ``bench.weights`` and the run's key, as the program's did.

``precision="control"`` is the lower-precision control, the model computed in
int8: every weight matrix rounded to int8 steps with one scale per output
channel, and the activation entering each matrix product rounded to int8
steps with one scale per row (its last axis is the one contracted).

``Reference`` is the entry point that ``bench/reference/__init__.py`` states.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import leaf

Cfg = dict[str, Any]


def int8_round(w: jax.Array, contract: tuple[int, ...]) -> jax.Array:
    """Round ``w`` to int8 steps, one scale per output channel (the axes
    not in ``contract``)."""
    amax = jnp.max(jnp.abs(w), axis=contract, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


class Dense:
    """``Dense(m).forward(key, tokens, read)``: ``m`` is the configuration's
    ``model`` section, ``key`` the run's weight key."""

    def __init__(self, m: Cfg, *, weight_dtype=jnp.bfloat16, precision: str = "reference"):
        self.m, self.wdt = m, weight_dtype
        self.control = precision == "control"
        self.ct = jnp.bfloat16 if self.control else jnp.float32
        d, H = m["d_model"], m["num_heads"]
        self.hd = m.get("head_dim") or d // H
        self.eps = m.get("norm_eps", 1e-6)
        self._layer = jax.jit(self.layer)
        self._embed = jax.jit(self.embed)
        self._logits = jax.jit(self.logits)

    def w(self, key, name: str, layer, shape, contract: tuple[int, ...] = ()) -> jax.Array:
        x = leaf(key, name, layer, shape, self.wdt).astype(jnp.float32)
        if self.control and contract:
            x = int8_round(x, contract)
        return x

    def mm(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        if self.control:
            a = int8_round(a, (a.ndim - 1,))
        return jnp.einsum(spec, a.astype(self.ct), b.astype(self.ct),
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    def rms(self, x: jax.Array, scale: jax.Array) -> jax.Array:
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps) * scale

    def rope(self, x: jax.Array, pos: jax.Array) -> jax.Array:
        """x: (R, T, heads, hd); pairs (i, i + hd/2) rotate by pos * theta^(-2i/hd)."""
        half = self.hd // 2
        inv = 1.0 / (self.m["rope_theta"] ** (np.arange(half) * 2.0 / self.hd))
        ang = pos[:, :, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(self, key, x, layer, window: int = 0):
        m, hd = self.m, self.hd
        d, H, KV = m["d_model"], m["num_heads"], m["num_kv_heads"]
        R, T, _ = x.shape
        q = self.mm("rtd,dhk->rthk", x, self.w(key, "attn/w_q", layer, (d, H, hd), (0,)))
        k = self.mm("rtd,dhk->rthk", x, self.w(key, "attn/w_k", layer, (d, KV, hd), (0,)))
        v = self.mm("rtd,dhk->rthk", x, self.w(key, "attn/w_v", layer, (d, KV, hd), (0,)))
        pos = jnp.broadcast_to(jnp.arange(T)[None], (R, T))
        q, k = self.rope(q, pos), self.rope(k, pos)
        g = H // KV                                   # query head h reads kv head h // g
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        s = self.mm("rqhk,rshk->rhqs", q, k) / math.sqrt(hd)
        i = jnp.arange(T)
        allowed = i[None, :] <= i[:, None]
        if window:
            allowed &= i[:, None] - i[None, :] < window
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        o = self.mm("rhqs,rshk->rqhk", p, v)
        return self.mm("rqhk,hkd->rqd", o, self.w(key, "attn/w_o", layer, (H, hd, d), (0, 1)))

    def mlp(self, key, x, layer):
        d, f = self.m["d_model"], self.m["d_ff"]
        gate = self.mm("rtd,df->rtf", x, self.w(key, "mlp/w_gate", layer, (d, f), (0,)))
        up = self.mm("rtd,df->rtf", x, self.w(key, "mlp/w_up", layer, (d, f), (0,)))
        return self.mm("rtf,fd->rtd", jax.nn.silu(gate) * up,
                       self.w(key, "mlp/w_down", layer, (f, d), (0,)))

    def layer(self, key, x, layer):
        d = self.m["d_model"]
        x = x + self.attention(key, self.rms(x, self.w(key, "ln1/scale", layer, (d,))), layer)
        return x + self.mlp(key, self.rms(x, self.w(key, "ln2/scale", layer, (d,))), layer)

    def embed(self, key, tokens):
        m = self.m
        return self.w(key, "embedding/embed", -1, (m["vocab_size"], m["d_model"]))[tokens]

    def logits(self, key, x):
        m = self.m
        x = self.rms(x, self.w(key, "final_norm/scale", -1, (m["d_model"],)))
        name = "embedding/embed" if m.get("tie_embeddings") else "embedding/unembed"
        table = self.w(key, name, -1, (m["vocab_size"], m["d_model"]), (1,))
        return self.mm("rtd,vd->rtv", x, table)

    def forward(self, key: jax.Array, tokens: np.ndarray, read: slice) -> jax.Array:
        """Logits ``(R, len(read), V)`` at positions ``read`` of ``tokens`` (R, T)."""
        x = self._embed(key, jnp.asarray(tokens))
        for i in range(self.m["num_layers"]):
            x = self._layer(key, x, jnp.int32(i))
        return self._logits(key, x[:, read])


Reference = Dense
