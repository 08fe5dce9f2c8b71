"""Plain float32 training steps of a Hymba-style hybrid decoder.

Each layer runs grouped-query attention (full on the global layers, a
sliding window on the others) and a Mamba-2 mixer side by side on the same
normed input, averages the two with learned per-channel weights, and adds a
SwiGLU MLP; logits come from an untied output matrix.  The mixer's scan is
the plain recurrence, one position at a time.  Written from that
description, not from the program's code.  A step runs layer by layer so
that it fits beside the program's freed state: the forward pass keeps each
layer's input, the backward pass takes each layer's ``jax.vjp`` in reverse
(one compiled layer program; the attention window is one of its inputs).
AdamW with global-norm clipping and a warmup-cosine schedule follows the
configuration's hyperparameters.

``precision="control"`` computes every matrix product in int8 steps (weights
one scale per output channel, activations one scale per row; gradients pass
the rounding straight through), the control that a run's limits must
separate from the program.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import int8_round
from bench.weights import leaf

Cfg = dict[str, Any]


def _ste(x, contract):
    """int8 steps forward, the identity backward (a straight-through
    estimator, as quantised training computes gradients)."""
    return x + jax.lax.stop_gradient(int8_round(x, contract) - x)


class Hybrid:
    def __init__(self, m: Cfg, *, precision: str = "reference"):
        self.m = m
        self.control = precision == "control"
        d, H = m["d_model"], m["num_heads"]
        self.hd = m.get("head_dim") or d // H
        s = m["ssm"]
        self.din = s["expand"] * d
        self.N, self.K, self.P = s["d_state"], s["d_conv"], s["head_dim"]
        self.nh = self.din // self.P
        g = set(m.get("global_layers", ()))
        self.windows = np.array([0 if i in g else m["sliding_window"]
                                 for i in range(m["num_layers"])], np.int32)

    # -- weights: (name, shape) of each layer leaf, drawn by bench.weights --------

    def layer_shapes(self) -> dict[str, tuple[int, ...]]:
        m, d, hd = self.m, self.m["d_model"], self.hd
        H, KV, f = m["num_heads"], m["num_kv_heads"], m["d_ff"]
        C = self.din + 2 * self.N
        return {
            "ln1/scale": (d,), "ln2/scale": (d,), "beta_attn": (d,), "beta_ssm": (d,),
            "attn/w_q": (d, H, hd), "attn/w_k": (d, KV, hd), "attn/w_v": (d, KV, hd),
            "attn/w_o": (H, hd, d),
            "mamba/w_in": (d, 2 * self.din + 2 * self.N + self.nh),
            "mamba/conv_w": (C, self.K), "mamba/conv_b": (C,), "mamba/a_log": (self.nh,),
            "mamba/dt_bias": (self.nh,), "mamba/d_skip": (self.nh,),
            "mamba/norm_scale": (self.din,), "mamba/w_out": (self.din, d),
            "mlp/w_gate": (d, f), "mlp/w_up": (d, f), "mlp/w_down": (f, d),
        }

    def top_shapes(self) -> dict[str, tuple[int, ...]]:
        V, d = self.m["vocab_size"], self.m["d_model"]
        return {"embedding/embed": (V, d), "embedding/unembed": (V, d), "final_norm/scale": (d,)}

    def init_top(self, key) -> dict[str, jax.Array]:
        return {n: leaf(key, n, -1, s, jnp.float32) for n, s in self.top_shapes().items()}

    def init_layer(self, key, i) -> dict[str, jax.Array]:
        return {n: leaf(key, n, i, s, jnp.float32) for n, s in self.layer_shapes().items()}

    # -- pieces ------------------------------------------------------------------------

    def mm(self, spec, a, b):
        if self.control:
            a = _ste(a, (a.ndim - 1,))
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    def w(self, p, name, contract=()):
        x = p[name]
        return _ste(x, contract) if self.control and contract else x

    @staticmethod
    def rms(x, scale, eps=1e-6):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale

    def rope(self, x, pos):
        half = self.hd // 2
        inv = 1.0 / (self.m["rope_theta"] ** (np.arange(half) * 2.0 / self.hd))
        ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(self, p, x, window):
        m, hd = self.m, self.hd
        H, KV = m["num_heads"], m["num_kv_heads"]
        B, T, _ = x.shape
        q = self.mm("btd,dhk->bthk", x, self.w(p, "attn/w_q", (0,)))
        k = self.mm("btd,dhk->bthk", x, self.w(p, "attn/w_k", (0,)))
        v = self.mm("btd,dhk->bthk", x, self.w(p, "attn/w_v", (0,)))
        pos = jnp.arange(T)
        q, k = self.rope(q, pos), self.rope(k, pos)
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
        s = self.mm("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
        allowed = (pos[None, :] <= pos[:, None]) & (
            (window == 0) | (pos[:, None] - pos[None, :] < window))
        prob = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        o = self.mm("bhqs,bshk->bqhk", prob, v)
        return self.mm("bqhk,hkd->bqd", o, self.w(p, "attn/w_o", (0, 1)))

    def mamba(self, p, x):
        din, N, K, P, nh = self.din, self.N, self.K, self.P, self.nh
        B, T, _ = x.shape
        zx = self.mm("btd,de->bte", x, self.w(p, "mamba/w_in", (0,)))
        z, xs, b, c, dt = (zx[..., :din], zx[..., din:2 * din], zx[..., 2 * din:2 * din + N],
                           zx[..., 2 * din + N:2 * din + 2 * N], zx[..., 2 * din + 2 * N:])
        u = jnp.concatenate([xs, b, c], -1)
        up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        conv_w = p["mamba/conv_w"]                      # (C, K); tap K-1 is the newest
        conv = sum(up[:, k:k + T] * conv_w[:, k] for k in range(K)) + p["mamba/conv_b"]
        conv = jax.nn.silu(conv)
        xs, b, c = conv[..., :din], conv[..., din:din + N], conv[..., din + N:]
        xh = xs.reshape(B, T, nh, P)
        dt = jax.nn.softplus(dt + p["mamba/dt_bias"])   # (B, T, nh)
        decay = jnp.exp(dt * -jnp.exp(p["mamba/a_log"]))

        def step(state, inp):                                 # state (B, nh, P, N)
            a_t, x_t, b_t, c_t = inp
            state = state * a_t[..., None, None] + x_t[..., None] * b_t[:, None, None, :]
            return state, jnp.einsum("bhpn,bn->bhp", state, c_t,
                                     precision=jax.lax.Precision.HIGHEST)

        xdt = xh * dt[..., None]
        seq = (decay.swapaxes(0, 1), xdt.swapaxes(0, 1), b.swapaxes(0, 1), c.swapaxes(0, 1))
        _, y = jax.lax.scan(step, jnp.zeros((B, nh, P, N), jnp.float32), seq)
        y = y.swapaxes(0, 1) + xh * p["mamba/d_skip"][:, None]
        g = y.reshape(B, T, din) * jax.nn.silu(z)
        g = self.rms(g, p["mamba/norm_scale"])
        return self.mm("bte,ed->btd", g, self.w(p, "mamba/w_out", (0,)))

    def mlp(self, p, x):
        gate = self.mm("btd,df->btf", x, self.w(p, "mlp/w_gate", (0,)))
        up = self.mm("btd,df->btf", x, self.w(p, "mlp/w_up", (0,)))
        return self.mm("btf,fd->btd", jax.nn.silu(gate) * up, self.w(p, "mlp/w_down", (0,)))

    def layer(self, p, x, window):
        """One layer; ``p`` holds that layer's weights, ``window`` 0 = full."""
        h = self.rms(x, p["ln1/scale"])
        y = 0.5 * (self.attention(p, h, window) * p["beta_attn"]
                   + self.mamba(p, h) * p["beta_ssm"])
        x = x + y
        return x + self.mlp(p, self.rms(x, p["ln2/scale"]))

    def head_loss(self, top, x, tokens):
        """Mean next-token cross-entropy over every position but the last."""
        x = self.rms(x, top["final_norm/scale"])
        logits = self.mm("btd,vd->btv", x, self.w(top, "embedding/unembed", (1,)))
        lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)


def schedule(opt: dict, step: int) -> float:
    """Warmup then cosine decay to ``min_lr_ratio`` of ``lr``; ``step`` from 1."""
    w, total = opt["warmup_steps"], opt["total_steps"]
    if step < w:
        return opt["lr"] * step / w
    t = min(max((step - w) / max(total - w, 1), 0.0), 1.0)
    return opt["lr"] * (opt["min_lr_ratio"]
                        + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + math.cos(math.pi * t)))


def adamw(opt: dict, p: dict, g: dict, m: dict, v: dict, lr, step):
    """One AdamW step with global-norm clipping; traceable.  Returns
    (params, m, v, the gradient as clipped)."""
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    out = ({}, {}, {}, {})
    for k in p:
        gk = g[k] * scale
        mk = b1 * m[k] + (1 - b1) * gk
        vk = b2 * v[k] + (1 - b2) * gk * gk
        upd = (mk / (1 - b1 ** step)) / (jnp.sqrt(vk / (1 - b2 ** step)) + opt["eps"])
        out[0][k] = p[k] - lr * (upd + opt["weight_decay"] * p[k])
        out[1][k], out[2][k], out[3][k] = mk, vk, gk
    return out


def first_steps(m: Cfg, key, batches: list[np.ndarray], opt: dict, devices,
                precision: str = "reference") -> dict:
    """Train ``len(batches)`` steps from the seed's weights.  Returns the
    losses, each leaf's norm (per layer) of the first clipped gradient, and
    of the change of the weights over all the steps."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    model = Hybrid(m, precision=precision)
    L = m["num_layers"]
    mesh = Mesh(np.array(devices), ("d",))
    n = len(devices)

    def spec(shape):                    # the first axis that splits evenly, else replicated
        for ax, size in enumerate(shape):
            if size % n == 0:
                return NamedSharding(mesh, P(*([None] * ax + ["d"])))
        return NamedSharding(mesh, P())

    top_sh = {k: spec(s) for k, s in model.top_shapes().items()}
    lay_sh = {k: spec(s) for k, s in model.layer_shapes().items()}
    rows = NamedSharding(mesh, P("d") if len(batches[0]) % n == 0 else P())
    init_top = jax.jit(model.init_top, out_shardings=top_sh)
    init_layer = jax.jit(model.init_layer, out_shardings=lay_sh)
    embed = jax.jit(lambda top, t: top["embedding/embed"][t], out_shardings=rows)
    fwd = jax.jit(model.layer, out_shardings=rows)
    bwd = jax.jit(lambda lp, x, w, ct: jax.vjp(lambda lp, x: model.layer(lp, x, w), lp, x)[1](ct),
                  out_shardings=(lay_sh, rows))
    head = jax.jit(jax.value_and_grad(model.head_loss, argnums=(0, 1)),
                   out_shardings=(None, (top_sh, rows)))
    embed_bwd = jax.jit(lambda top, t, ct: jax.vjp(
        lambda e: e[t], top["embedding/embed"])[1](ct)[0], out_shardings=top_sh["embedding/embed"])

    def flat(top, layers):
        out = dict(top)
        for i, lp in enumerate(layers):
            out.update({f"{i}:{k}": v for k, v in lp.items()})
        return out

    sh = flat(top_sh, [lay_sh] * L)
    update = jax.jit(lambda p, g, mm, vv, lr, step: adamw(opt, p, g, mm, vv, lr, step),
                     out_shardings=(sh, sh, sh, sh), donate_argnums=(0, 2, 3))
    zeros = jax.jit(lambda t: {k: jnp.zeros_like(x) for k, x in t.items()}, out_shardings=sh)
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(x * x)) for k, x in t.items()})
    windows = [jnp.int32(w) for w in model.windows]

    p = flat(init_top(key), [init_layer(key, jnp.int32(i)) for i in range(L)])
    mom, vel = zeros(p), zeros(p)
    losses, grad1 = [], None
    for step, tokens in enumerate(batches, start=1):
        tokens = jax.device_put(jnp.asarray(tokens), rows)
        top = {k: p[k] for k in model.top_shapes()}
        layers = [{k: p[f"{i}:{k}"] for k in model.layer_shapes()} for i in range(L)]
        xs = [embed(top, tokens)]
        for i in range(L):
            xs.append(fwd(layers[i], xs[i], windows[i]))
        loss, (g_top, ct) = head(top, xs[-1], tokens)
        g_layers = [None] * L
        for i in reversed(range(L)):
            g_layers[i], ct = bwd(layers[i], xs[i], windows[i], ct)
        g_top = dict(g_top, **{"embedding/embed": g_top["embedding/embed"]
                               + embed_bwd(top, tokens, ct)})
        del xs, ct, top, layers
        p, mom, vel, clipped = update(p, flat(g_top, g_layers), mom, vel,
                                      schedule(opt, step), float(step))
        if step == 1:
            grad1 = {k: float(v) for k, v in norms(clipped).items()}
        losses.append(float(loss))
        del g_top, g_layers, clipped
    p0 = flat(init_top(key), [init_layer(key, jnp.int32(i)) for i in range(L)])
    change = norms({k: p[k] - p0[k] for k in p})
    return {"losses": losses, "grad1": grad1, "change3": {k: float(v) for k, v in change.items()}}
