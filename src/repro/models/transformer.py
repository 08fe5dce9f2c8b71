"""Model assembly: layer blocks, scan-over-layers, train/prefill/decode.

All ten assigned architectures run through this module (whisper adds an
encoder in ``whisper.py``).  Layers are grouped into homogeneous stacks
(``layer_groups``) so ``lax.scan`` keeps HLO size O(1) in depth; groups
exist because some archs interleave heterogeneous layers (DeepSeek/Kimi's
leading dense layer, Hymba's three global-attention layers).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ModelConfig
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    embed_tokens,
    init_embedding,
    init_mlp,
    init_norm,
    logits_matmul,
)

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    name: str
    count: int
    kind: str          # dense | moe | ssm | hybrid
    window: int = 0    # sliding window (0 = full attention)


def layer_groups(cfg: ModelConfig) -> list[LayerGroup]:
    if cfg.family == "ssm":
        return [LayerGroup("layers", cfg.num_layers, "ssm")]
    if cfg.family == "hybrid":
        groups: list[LayerGroup] = []
        gl = set(cfg.global_layers)
        i, g = 0, 0
        while i < cfg.num_layers:
            if i in gl:
                groups.append(LayerGroup(f"global{g}", 1, "hybrid", window=0))
                g += 1
                i += 1
            else:
                j = i
                while j < cfg.num_layers and j not in gl:
                    j += 1
                groups.append(
                    LayerGroup(f"local{len(groups)}", j - i, "hybrid",
                               window=cfg.sliding_window)
                )
                i = j
        return groups
    if cfg.family == "moe":
        fd = cfg.moe.first_dense
        out = []
        if fd:
            out.append(LayerGroup("dense0", fd, "dense"))
        out.append(LayerGroup("moe", cfg.num_layers - fd, "moe"))
        return out
    return [LayerGroup("layers", cfg.num_layers, "dense")]


# -- layer init ---------------------------------------------------------------

def _init_layer(cfg: ModelConfig, group: LayerGroup, key) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {}
    if group.kind == "ssm":
        p["ln1"] = init_norm(cfg, cfg.d_model)
        p["mamba"] = ssm_mod.init_mamba(cfg, ks[0])
        return p
    p["ln1"] = init_norm(cfg, cfg.d_model)
    p["attn"] = attn_mod.init_attention(cfg, ks[0])
    p["ln2"] = init_norm(cfg, cfg.d_model)
    if group.kind == "hybrid":
        p["mamba"] = ssm_mod.init_mamba(cfg, ks[1])
        p["beta_attn"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
        p["beta_ssm"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
        p["mlp"] = init_mlp(cfg, ks[2], cfg.d_model, cfg.d_ff)
    elif group.kind == "moe":
        p["moe"] = moe_mod.init_moe(cfg, ks[1])
    else:
        p["mlp"] = init_mlp(cfg, ks[1], cfg.d_model, cfg.d_ff)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, len(layer_groups(cfg)) + 2)
    params: Params = {"embedding": init_embedding(cfg, ks[0])}
    for i, group in enumerate(layer_groups(cfg)):
        gkeys = jax.random.split(ks[i + 1], group.count)
        params[group.name] = jax.vmap(
            lambda k, g=group: _init_layer(cfg, g, k)
        )(gkeys)
    params["final_norm"] = init_norm(cfg, cfg.d_model)
    return params


# -- layer apply -----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RunCtx:
    """Per-call distribution context (mesh for EP, decode flags).

    ``decode`` marks a call that takes no gradient (set by
    :func:`decode_step`; a serving caller may set it for its prefill too);
    every call with a cache is one.  Expert layers then take the served path.

    ``empty_cache`` is set by :func:`prefill` alone: the cache it fills is
    empty and the positions start at 0, so full-attention layers attend over
    their own keys (the flash kernel, or latent attention's expanded form)
    and write them with one slice.
    """

    mesh: Any = None
    dp_axes: tuple[str, ...] = ("data",)
    ep_axis: str = "model"
    decode: bool = False
    empty_cache: bool = False


def _apply_layer(
    cfg: ModelConfig,
    group: LayerGroup,
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cache: Params | None,
    ctx: RunCtx,
) -> tuple[jax.Array, Params | None, jax.Array]:
    """Returns (x_out, new_cache, aux_loss).

    Each block runs under a ``jax.named_scope`` (``attn``, ``mlp``, ``moe``,
    ``ssm``; ``repro.models.scopes``) that holds its norm, its body and its
    residual add, so every op of the layer loop outside them is the scan's
    own slicing and stacking.
    """
    aux = jnp.zeros((), jnp.float32)
    if group.kind == "ssm":
        with jax.named_scope("ssm"):
            h = apply_norm(cfg, p["ln1"], x)
            y, new_cache = ssm_mod.apply_mamba(cfg, p["mamba"], h, cache=cache, ctx=ctx)
            return x + y, new_cache, aux

    new_cache: Params = {}
    if group.kind == "hybrid":
        a_cache = cache.get("attn") if cache else None
        s_cache = cache.get("ssm") if cache else None
        with jax.named_scope("attn"):
            h = apply_norm(cfg, p["ln1"], x)
            y_attn, a_new = attn_mod.apply_attention(
                cfg, p["attn"], h, positions=positions, causal=True,
                window=group.window, cache=a_cache, ctx=ctx,
            )
        with jax.named_scope("ssm"):
            y_ssm, s_new = ssm_mod.apply_mamba(cfg, p["mamba"], h, cache=s_cache, ctx=ctx)
        with jax.named_scope("attn"):  # the two heads' mix and the residual
            ct = cfg.compute_dtype
            y = 0.5 * (
                y_attn * p["beta_attn"].astype(ct) + y_ssm * p["beta_ssm"].astype(ct)
            )
            x = x + y
        with jax.named_scope("mlp"):
            h2 = apply_norm(cfg, p["ln2"], x)
            x = x + apply_mlp(cfg, p["mlp"], h2)
        if cache is not None:
            new_cache = {"attn": a_new, "ssm": s_new}
        return x, (new_cache if cache is not None else None), aux

    with jax.named_scope("attn"):
        h = apply_norm(cfg, p["ln1"], x)
        if cfg.mla is not None:
            y, a_new = attn_mod.apply_mla(
                cfg, p["attn"], h, positions=positions, cache=cache, ctx=ctx
            )
        else:
            y, a_new = attn_mod.apply_attention(
                cfg, p["attn"], h, positions=positions, causal=True,
                window=group.window, cache=cache, ctx=ctx,
            )
        x = x + y
    if group.kind == "moe":
        with jax.named_scope("moe"):
            h2 = apply_norm(cfg, p["ln2"], x)
            y2, aux = moe_mod.apply_moe(
                cfg, p["moe"], h2,
                mesh=ctx.mesh, dp_axes=ctx.dp_axes, ep_axis=ctx.ep_axis,
                serving=ctx.decode or cache is not None,
            )
            return x + y2, a_new, aux
    with jax.named_scope("mlp"):
        h2 = apply_norm(cfg, p["ln2"], x)
        return x + apply_mlp(cfg, p["mlp"], h2), a_new, aux


def _remat_wrap(cfg: ModelConfig, fn: Callable) -> Callable:
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return fn


def _scan_group(
    cfg: ModelConfig,
    group: LayerGroup,
    gparams: Params,
    x: jax.Array,
    positions: jax.Array,
    gcache: Params | None,
    ctx: RunCtx,
):
    """Scan a homogeneous stack of layers; cache (if any) is stacked too."""

    def body(carry, layer_in):
        xc, aux_acc = carry
        lp, lcache = layer_in
        y, new_cache, aux = _apply_layer(cfg, group, lp, xc, positions, lcache, ctx)
        return (y, aux_acc + aux), new_cache

    body = _remat_wrap(cfg, body)
    if gcache is None:
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (gparams, None)
        )
        return x, None, aux
    if not cfg.scan_layers:
        # unrolled: per-layer cache slices update in place; the scanned
        # ys-buffer variant copies the whole stacked cache per iteration
        aux = jnp.zeros((), jnp.float32)
        new_layers = []
        for i in range(group.count):
            lp = jax.tree.map(lambda p: p[i], gparams)
            lcache = jax.tree.map(lambda c: c[i], gcache)
            x, nc, aux_i = _apply_layer(cfg, group, lp, x, positions, lcache, ctx)
            aux = aux + aux_i
            new_layers.append(nc)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_layers)
        return x, new_cache, aux
    (x, aux), new_cache = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (gparams, gcache)
    )
    return x, new_cache, aux


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                # (B, S) int32
    *,
    positions: jax.Array | None = None,
    cache: Params | None = None,      # {group: stacked layer caches}
    ctx: RunCtx = RunCtx(),
    patch_embeds: jax.Array | None = None,  # vlm stub input
    frame_embeds: jax.Array | None = None,  # audio stub (enc-dec handled upstream)
) -> tuple[jax.Array, Params | None, jax.Array]:
    """Returns (hidden_states, new_cache, aux_loss)."""
    from repro.models.common import shard_hint

    B, S = tokens.shape
    x = embed_tokens(cfg, params["embedding"], tokens)
    # pin activations to (dp, -, -): the vocab-sharded embedding gather
    # otherwise triggers an SPMD replication fallback that propagates
    # replicated layouts into the layer stack (§Perf iteration 1)
    x = shard_hint(x, ctx, ("dp", None, None))
    if patch_embeds is not None:
        n_img = patch_embeds.shape[1]
        x = jax.lax.dynamic_update_slice(
            x, patch_embeds.astype(x.dtype), (0, 0, 0)
        ) if S >= n_img else x
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Params = {}
    for group in layer_groups(cfg):
        gcache = cache.get(group.name) if cache is not None else None
        x, gnew, aux = _scan_group(
            cfg, group, params[group.name], x, positions, gcache, ctx
        )
        aux_total = aux_total + aux
        if cache is not None:
            new_cache[group.name] = gnew
    with jax.named_scope("logits"):
        x = apply_norm(cfg, params["final_norm"], x)
    return x, (new_cache if cache is not None else None), aux_total


# -- public step functions ------------------------------------------------------------

def _nll(cfg: ModelConfig, emb, x: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token negative log likelihood; chunked over S when configured so
    the (B, S_chunk, V) logits block -- not (B, S, V) -- is the live buffer."""
    B, S, d = x.shape
    C = cfg.logits_chunk
    if C <= 0 or S % C != 0 or S <= C:
        logits = logits_matmul(cfg, emb, x).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return lse - tl

    nc = S // C
    xc = x.reshape(B, nc, C, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, nc, C).transpose(1, 0, 2)

    def body(_, inp):
        xq, tq = inp
        logits = logits_matmul(cfg, emb, xq).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, tq[..., None], axis=-1)[..., 0]
        return None, lse - tl

    _, nll = jax.lax.scan(body, None, (xc, tc))
    return nll.transpose(1, 0, 2).reshape(B, S)


def loss_fn(
    cfg: ModelConfig,
    params: Params,
    batch: dict[str, jax.Array],
    ctx: RunCtx = RunCtx(),
) -> jax.Array:
    """Next-token cross-entropy (+ router aux for MoE)."""
    tokens = batch["tokens"]
    x, _, aux = forward(
        cfg, params, tokens, ctx=ctx,
        patch_embeds=batch.get("patch_embeds"),
    )
    targets = batch.get("labels")
    if targets is None:
        targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    nll = _nll(cfg, params["embedding"], x, targets)
    mask = jnp.ones_like(nll).at[:, -1].set(0.0)
    loss = (nll * mask).sum() / mask.sum()
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_coef * aux
    return loss


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Stacked per-group decode caches."""
    cache: Params = {}
    for group in layer_groups(cfg):
        if group.kind == "ssm":
            one = lambda: ssm_mod.init_mamba_cache(cfg, batch)
        elif group.kind == "hybrid":
            window = group.window
            one = lambda window=window: {
                "attn": attn_mod.init_kv_cache(cfg, batch, max_len, window),
                "ssm": ssm_mod.init_mamba_cache(cfg, batch),
            }
        elif cfg.mla is not None:
            one = lambda: attn_mod.init_mla_cache(cfg, batch, max_len)
        else:
            one = lambda: attn_mod.init_kv_cache(cfg, batch, max_len)
        cache[group.name] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (group.count, *x.shape)), one()
        )
    return cache


def decode_step(
    cfg: ModelConfig,
    params: Params,
    cache: Params,
    tokens: jax.Array,        # (B, 1)
    positions: jax.Array,     # (B, 1) absolute positions
    ctx: RunCtx = RunCtx(),
) -> tuple[jax.Array, Params]:
    ctx = dataclasses.replace(ctx, decode=True)
    x, new_cache, _ = forward(
        cfg, params, tokens, positions=positions, cache=cache, ctx=ctx
    )
    logits = logits_matmul(cfg, params["embedding"], x[:, -1:])
    return logits, new_cache


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,        # (B, S)
    cache: Params,
    ctx: RunCtx = RunCtx(),
    patch_embeds: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    """Run the full prompt through an empty cache, filling it."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    ctx = dataclasses.replace(ctx, empty_cache=True)
    x, new_cache, _ = forward(
        cfg, params, tokens, positions=positions, cache=cache, ctx=ctx,
        patch_embeds=patch_embeds,
    )
    logits = logits_matmul(cfg, params["embedding"], x[:, -1:])
    return logits, new_cache
