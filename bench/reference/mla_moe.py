"""Plain float32 forward pass of a DeepSeek-V2-style model: multi-head latent
attention and routed experts with shared experts.

Written from the architecture's description (DeepSeek-V2, arXiv:2405.04434,
and the published ``config.json``), not from the program's code, and run one
layer at a time so that the whole depth fits beside its activations.  Per
layer: pre-norm RMSNorm; latent attention with full-rank queries, the
compressed ``c`` RMS-normed before its up-projections to per-head keys and
values, decoupled rotary keys shared by all heads, YaRN-scaled rotary and
softmax scale; then a dense SwiGLU MLP (the first ``moe.first_dense``
layers) or the expert layer: a softmax router over all ``num_experts``, the
top ``top_k`` weights (renormalised only under ``norm_topk_prob``, times
``routed_scaling_factor``), the routed SwiGLU experts and the shared experts
(one SwiGLU of ``num_shared * expert_d_ff``) for every token.  Untied
embeddings.  The weights come from ``bench.weights`` and the run's key, as
the program's did.

Departures from the published model, each the same in the program:

- The chip's share of an expert-parallel layer: the router scores every
  expert, but only the ``num_held`` experts from ``first_held`` are computed;
  what the experts held elsewhere would add is left out.
- The rotary pairs dimension ``i`` with ``i + rope/2`` (half-split), where
  the published code rotates interleaved pairs ``(2i, 2i + 1)``: a fixed
  relabelling of the rope columns of the query and key projections, which
  under random weights is the same model.
- Random weights from the seed; every norm scale is ones.

``precision="control"`` is the lower-precision control of ``dense.py``: the
model computed in int8.  ``Reference`` is the entry point that
``bench/reference/__init__.py`` states.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import Dense


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


class MlaMoe(Dense):
    """``MlaMoe(m).forward(key, tokens, read)``: ``m`` is the configuration's
    ``model`` section, ``key`` the run's weight key."""

    def __init__(self, m, *, weight_dtype=jnp.bfloat16, precision: str = "reference"):
        super().__init__(m, weight_dtype=weight_dtype, precision=precision)
        a = m["mla"]
        self.r, self.rope = a["kv_lora_rank"], a["qk_rope_dim"]
        self.nope, self.vd = a["qk_nope_dim"], a["v_head_dim"]
        if a.get("q_lora_rank"):
            raise ValueError("a compressed query projection (q_lora_rank) is not implemented")
        self.yarn = m.get("yarn")
        factor = self.yarn["factor"] if self.yarn else 1.0
        all_dim = self.yarn.get("mscale_all_dim", 0.0) if self.yarn else 0.0
        self.softmax_scale = (self.nope + self.rope) ** -0.5 * _mscale(factor, all_dim) ** 2
        self._moe_layer = jax.jit(self.moe_layer)

    def rotary(self, x: jax.Array, T: int) -> jax.Array:
        """x: (R, T, heads, rope) at positions 0..T-1; YaRN: the frequency of
        pair i is theta^(-2i/rope), divided by ``factor`` below ``beta_slow``
        rotations over the original context, kept above ``beta_fast``,
        linear between; cos and sin times mscale / mscale_all_dim."""
        dim, half = self.rope, self.rope // 2
        theta = self.m["rope_theta"]
        inv = theta ** (-np.arange(half) * 2.0 / dim)
        gain = 1.0
        if self.yarn:
            y = self.yarn

            def at(rot):   # the pair index that turns ``rot`` times over the original context
                return dim * math.log(y["original_max_position"] / (rot * 2 * math.pi)) / (
                    2 * math.log(theta))

            lo = max(math.floor(at(y["beta_fast"])), 0)
            hi = min(math.ceil(at(y["beta_slow"])), dim - 1)
            ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
            inv = inv * (1.0 - ramp) + inv / y["factor"] * ramp
            gain = _mscale(y["factor"], y.get("mscale", 1.0)) / _mscale(
                y["factor"], y.get("mscale_all_dim", 0.0))
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
        cos = (jnp.cos(ang) * gain)[None, :, None, :]
        sin = (jnp.sin(ang) * gain)[None, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(self, key, x, layer, window: int = 0):
        m = self.m
        d, H = m["d_model"], m["num_heads"]
        r, rope, nope, vd = self.r, self.rope, self.nope, self.vd
        R, T, _ = x.shape
        q = self.mm("rtd,dhk->rthk", x, self.w(key, "attn/w_q", layer, (d, H, nope + rope), (0,)))
        ckr = self.mm("rtd,dc->rtc", x, self.w(key, "attn/w_dkv", layer, (d, r + rope), (0,)))
        c = self.rms(ckr[..., :r], self.w(key, "attn/kv_norm/scale", layer, (r,)))
        q_nope, q_rope = q[..., :nope], self.rotary(q[..., nope:], T)
        k_rope = self.rotary(ckr[:, :, None, r:], T)[:, :, 0]          # one for all heads
        k_nope = self.mm("rtc,chk->rthk", c, self.w(key, "attn/w_uk", layer, (r, H, nope), (0,)))
        v = self.mm("rtc,chk->rthk", c, self.w(key, "attn/w_uv", layer, (r, H, vd), (0,)))
        s = (self.mm("rqhk,rshk->rhqs", q_nope, k_nope)
             + self.mm("rqhk,rsk->rhqs", q_rope, k_rope)) * self.softmax_scale
        i = jnp.arange(T)
        p = jax.nn.softmax(jnp.where(i[None, :] <= i[:, None], s, -jnp.inf), axis=-1)
        o = self.mm("rhqs,rshk->rqhk", p, v)
        return self.mm("rqhk,hkd->rqd", o, self.w(key, "attn/w_o", layer, (H, vd, d), (0, 1)))

    def swiglu(self, x, gate, up, down):
        return self.mm("rtf,fd->rtd", jax.nn.silu(self.mm("rtd,df->rtf", x, gate))
                       * self.mm("rtd,df->rtf", x, up), down)

    def moe(self, key, x, layer):
        """The expert layer's output for ``x`` (R, T, d): the held experts'
        part of the routed sum, plus the shared experts."""
        m, mo = self.m, self.m["moe"]
        d, f, E, k = m["d_model"], mo["expert_d_ff"], mo["num_experts"], mo["top_k"]
        first, held = mo.get("first_held", 0), mo.get("num_held") or E
        probs = jax.nn.softmax(self.mm("rtd,de->rte", x, self.w(key, "moe/router", layer,
                                                                 (d, E), (0,))), axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        if mo.get("norm_topk_prob", True):
            top_w = top_w / top_w.sum(-1, keepdims=True)
        top_w = top_w * mo.get("routed_scaling_factor", 1.0)
        weight = (jax.nn.one_hot(top_i, E) * top_w[..., None]).sum(-2)[..., first:first + held]
        gate = self.w(key, "moe/w_gate", layer, (held, d, f), (1,))
        up = self.w(key, "moe/w_up", layer, (held, d, f), (1,))
        down = self.w(key, "moe/w_down", layer, (held, f, d), (1,))
        y = sum(weight[..., e, None] * self.swiglu(x, gate[e], up[e], down[e])
                for e in range(held))
        fs = mo.get("num_shared", 0) * f
        if fs:
            y = y + self.swiglu(x, self.w(key, "moe/shared/w_gate", layer, (d, fs), (0,)),
                                self.w(key, "moe/shared/w_up", layer, (d, fs), (0,)),
                                self.w(key, "moe/shared/w_down", layer, (fs, d), (0,)))
        return y

    def moe_layer(self, key, x, layer):
        d = self.m["d_model"]
        x = x + self.attention(key, self.rms(x, self.w(key, "ln1/scale", layer, (d,))), layer)
        return x + self.moe(key, self.rms(x, self.w(key, "ln2/scale", layer, (d,))), layer)

    def forward(self, key: jax.Array, tokens: np.ndarray, read: slice) -> jax.Array:
        """Logits ``(R, len(read), V)`` at positions ``read`` of ``tokens`` (R, T)."""
        first_dense = self.m["moe"].get("first_dense", 0)
        with jax.default_matmul_precision("highest"):
            x = self._embed(key, jnp.asarray(tokens))
            for i in range(self.m["num_layers"]):
                step = self._layer if i < first_dense else self._moe_layer
                x = step(key, x, jnp.int32(i))
            return self._logits(key, x[:, read])


Reference = MlaMoe
