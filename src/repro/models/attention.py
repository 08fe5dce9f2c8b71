"""Attention: GQA/MQA, sliding-window, and MLA (DeepSeek latent attention).

The training/prefill path is a *chunked online-softmax* ("flash-style")
implementation in pure jnp: both query and key/value are tiled with
``lax.scan`` so the S x S score matrix never materializes -- this keeps the
dry-run memory analysis honest at 32K-512K context.  The Pallas kernel in
``repro.kernels.flash_attention`` (same math, MXU tiling) replaces it when
``cfg.attention_impl == "pallas"``, and always in a prefill into an empty
cache with full attention on one device (``RunCtx.empty_cache``): there it
attends over the prompt's own keys and values, which then fill the cache
with one slice.

Note on FLOPs: the chunked reference computes masked (non-causal) blocks
and masks them, so HLO FLOPs ~= 2x the causal-optimal count; the Pallas
kernel skips fully-masked blocks on the grid.  This shows up explicitly in
the roofline MODEL_FLOPS/HLO ratio and is called out in EXPERIMENTS.md.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import (
    apply_norm,
    apply_rope,
    init_norm,
    normal_init,
    yarn_softmax_factor,
)

Params = dict[str, Any]

NEG_INF = -1e30


# -- parameter init -----------------------------------------------------------

def init_attention(cfg, key) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    std = d**-0.5
    if cfg.mla is not None:
        m = cfg.mla
        q_dim = m.qk_nope_dim + m.qk_rope_dim
        p = {
            "w_q": normal_init(ks[0], (d, H, q_dim), std, cfg.param_dtype),
            "w_dkv": normal_init(
                ks[1], (d, m.kv_lora_rank + m.qk_rope_dim), std, cfg.param_dtype
            ),
            "w_uk": normal_init(
                ks[2], (m.kv_lora_rank, H, m.qk_nope_dim),
                m.kv_lora_rank**-0.5, cfg.param_dtype,
            ),
            "w_uv": normal_init(
                ks[3], (m.kv_lora_rank, H, m.v_head_dim),
                m.kv_lora_rank**-0.5, cfg.param_dtype,
            ),
            "w_o": normal_init(
                ks[4], (H, m.v_head_dim, d), (H * m.v_head_dim) ** -0.5,
                cfg.param_dtype,
            ),
            "kv_norm": init_norm(cfg, m.kv_lora_rank),
        }
        return p
    p = {
        "w_q": normal_init(ks[0], (d, H, hd), std, cfg.param_dtype),
        "w_k": normal_init(ks[1], (d, KV, hd), std, cfg.param_dtype),
        "w_v": normal_init(ks[2], (d, KV, hd), std, cfg.param_dtype),
        "w_o": normal_init(ks[3], (H, hd, d), (H * hd) ** -0.5, cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((H, hd), cfg.param_dtype)
        p["b_k"] = jnp.zeros((KV, hd), cfg.param_dtype)
        p["b_v"] = jnp.zeros((KV, hd), cfg.param_dtype)
    return p


# -- chunked online-softmax core ------------------------------------------------

def chunked_attention(
    q: jax.Array,           # (B, Sq, KV, G, hd)
    k: jax.Array,           # (B, Skv, KV, hd)
    v: jax.Array,           # (B, Skv, KV, hdv)
    *,
    causal: bool,
    window: int = 0,        # 0 = unlimited
    q_offset: Any = 0,      # scalar or (B,): absolute position of q[0]
    kv_len: Any = None,     # scalar or (B,): valid prefix length of k/v
    chunk: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Tiled attention; never materializes (Sq, Skv) for long sequences."""
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    hdv = v.shape[-1]
    scale = scale if scale is not None else hd**-0.5
    if Sq <= 4 and Skv > Sq:
        # Decode: single dense einsum over the cache.  Deliberate -- XLA SPMD
        # partitions softmax over a sequence-sharded KV cache (all-reduce of
        # max/sum), which a sequential scan over chunks cannot express.
        return _decode_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            kv_len=kv_len, scale=scale,
        )
    qc = min(chunk, Sq)
    kc = min(chunk, Skv)
    nq, nk = -(-Sq // qc), -(-Skv // kc)
    # pad to multiples
    q = jnp.pad(q, ((0, 0), (0, nq * qc - Sq), (0, 0), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0)))

    q = q.reshape(B, nq, qc, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    k = k.reshape(B, nk, kc, KV, hd).transpose(1, 0, 2, 3, 4)
    v = v.reshape(B, nk, kc, KV, hdv).transpose(1, 0, 2, 3, 4)

    # Normalize offsets/lengths to (B', 1) so masks broadcast as (B', qc, kc).
    q_off = jnp.atleast_1d(jnp.asarray(q_offset, jnp.int32)).reshape(-1, 1)
    valid_len = Skv if kv_len is None else kv_len
    valid = jnp.atleast_1d(jnp.asarray(valid_len, jnp.int32)).reshape(-1, 1)

    def q_block(iq, q_i):
        q_pos = q_off + iq * qc + jnp.arange(qc)[None, :]  # (B', qc)

        def kv_step(carry, inp):
            jk, k_j, v_j = inp
            m, l, acc = carry
            s = jnp.einsum(
                "bqkgh,bckh->bqkgc", q_i, k_j, preferred_element_type=jnp.float32
            ) * scale
            k_pos = jk * kc + jnp.arange(kc)
            mask = k_pos[None, None, :] < valid[:, :, None]  # (B', 1, kc)
            if causal:
                mask = mask & (k_pos[None, None, :] <= q_pos[:, :, None])
            if window > 0:
                mask = mask & (q_pos[:, :, None] - k_pos[None, None, :] < window)
            s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqkgc,bckh->bqkgh", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, qc, KV, G), NEG_INF, jnp.float32),
            jnp.zeros((B, qc, KV, G), jnp.float32),
            jnp.zeros((B, qc, KV, G, hdv), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (jnp.arange(nk), k, v))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(lambda t: q_block(t[0], t[1]), (jnp.arange(nq), q))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, KV, G, hdv)
    return out[:, :Sq].astype(v.dtype)


def _decode_attention(q, k, v, *, causal, window, q_offset, kv_len, scale):
    """Unchunked attention for tiny Sq against a (possibly huge) cache."""
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    q_off = jnp.atleast_1d(jnp.asarray(q_offset, jnp.int32)).reshape(-1, 1)
    valid_len = Skv if kv_len is None else kv_len
    valid = jnp.atleast_1d(jnp.asarray(valid_len, jnp.int32)).reshape(-1, 1)
    q_pos = q_off + jnp.arange(Sq)[None, :]             # (B', Sq)
    k_pos = jnp.arange(Skv)
    mask = k_pos[None, None, :] < valid[:, :, None]     # (B', 1, Skv)
    if causal:
        mask = mask & (k_pos[None, None, :] <= q_pos[:, :, None])
    if window > 0:
        mask = mask & (q_pos[:, :, None] - k_pos[None, None, :] < window)
    s = jnp.einsum(
        "bqkgh,bckh->bqkgc", q, k, preferred_element_type=jnp.float32
    ) * scale
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bqkgc,bckh->bqkgh", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(v.dtype)


# The flash kernel's blocks: a whole row of up to 512 queries against as many
# keys per grid step.  At S = 512 a layer is then B * H steps (384 for
# phi4-mini's batch of 16); the kernel's 128-wide defaults take 16 times as
# many, each paying the step's fixed cost (on a TPU v5e, at that shape:
# 0.86 ms a layer against 1.80 ms at 256 and 3.28 ms at 128).
FLASH_BLOCK = 512


def _fills_empty_cache(ctx) -> bool:
    """The call fills an empty cache from position 0 (``RunCtx.empty_cache``)
    on at most one device.  Under a larger mesh the prefill keeps the XLA
    path, which SPMD partitions."""
    if not getattr(ctx, "empty_cache", False):
        return False
    mesh = getattr(ctx, "mesh", None)
    return mesh is None or mesh.size == 1


def _flash_attention(q, k, v, *, causal: bool):
    """Self-attention of a whole sequence from position 0 in the Pallas
    flash kernel; model layout in and out."""
    from repro.kernels.flash_attention.ops import flash_attention_gqa

    # model layout q (B,S,KV,G,hd), k/v (B,S,KV,hd) -> kernel (B,H,S,hd)
    B, S, KV, G, hd = q.shape
    qk = q.transpose(0, 2, 3, 1, 4).reshape(B, KV * G, S, hd)
    out = flash_attention_gqa(
        qk, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, block_q=FLASH_BLOCK, block_k=FLASH_BLOCK,
    )
    return out.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4)


def _maybe_pallas_attention(cfg, q, k, v, *, causal, window, q_offset, kv_len):
    """Dispatch to the Pallas flash kernel when configured and applicable."""
    if (
        cfg.attention_impl == "pallas"
        and window == 0
        and kv_len is None
        and isinstance(q_offset, int)
        and q_offset == 0
    ):
        return _flash_attention(q, k, v, causal=causal)
    return chunked_attention(
        q, k, v,
        causal=causal, window=window, q_offset=q_offset, kv_len=kv_len,
        chunk=cfg.attention_chunk,
    )


# -- GQA full layer ----------------------------------------------------------------

def apply_attention(
    cfg,
    p: Params,
    x: jax.Array,                 # (B, S, d)
    *,
    positions: jax.Array,         # (B, S) absolute positions
    causal: bool = True,
    window: int = 0,
    cache: Params | None = None,  # decode KV cache
    cross_kv: tuple | None = None,  # (k, v) for cross attention
    ctx: Any = None,
) -> tuple[jax.Array, Params | None]:
    from repro.models.common import shard_hint

    ct = cfg.compute_dtype
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV
    B, S, _ = x.shape
    x = x.astype(ct)

    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(ct))
    if "b_q" in p:
        q = q + p["b_q"].astype(ct)
    if ctx is not None:
        # keep attention batch-parallel (heads shard only when they divide
        # TP); prevents replicated projection VJPs inside the chunk loops
        q = shard_hint(q, ctx, ("dp", None, "tp", None))

    if cross_kv is not None:
        k, v = cross_kv
        new_cache = None
        q = q.reshape(B, S, KV, G, hd)
        out = chunked_attention(
            q, k, v, causal=False, chunk=cfg.attention_chunk
        )
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["w_k"].astype(ct))
        v = jnp.einsum("bsd,dhk->bshk", x, p["w_v"].astype(ct))
        if "b_k" in p:
            k = k + p["b_k"].astype(ct)
            v = v + p["b_v"].astype(ct)
        if ctx is not None:
            k = shard_hint(k, ctx, ("dp", None, "tp", None))
            v = shard_hint(v, ctx, ("dp", None, "tp", None))
        if cfg.rope_theta > 0:  # 0 = learned/absolute positions (whisper)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

        if cache is not None and window > 0 and S > 1:
            # Windowed prefill: ring slots would not be position-addressable
            # for S > window, so compute windowed attention directly and fill
            # the ring with the last `window` tokens.
            q = q.reshape(B, S, KV, G, hd)
            out = chunked_attention(
                q, k, v, causal=True, window=window, chunk=cfg.attention_chunk
            )
            with jax.named_scope("kv_write"):
                new_cache = _fill_ring_cache(cache, k, v)
        elif cache is not None and window == 0 and _fills_empty_cache(ctx):
            # Prefill into an empty cache: the slots below S would hold
            # exactly these keys and values and the rest are masked, so the
            # kernel attends over them directly and the cache is not read.
            q = q.reshape(B, S, KV, G, hd)
            with jax.named_scope("flash"):
                out = _flash_attention(q, k, v, causal=True)
            with jax.named_scope("kv_write"):
                new_cache = _fill_linear_cache(cache, k, v)
        elif cache is not None:
            with jax.named_scope("kv_write"):
                k, v, new_cache, kv_len, q_offset, cache_causal = _update_kv_cache(
                    cache, k, v, positions, window, aligned=cfg.aligned_decode
                )
            q = q.reshape(B, S, KV, G, hd)
            out = chunked_attention(
                q, k, v,
                causal=cache_causal,  # ring caches mask via kv_len instead
                window=0,
                kv_len=kv_len,
                q_offset=q_offset,
                chunk=cfg.attention_chunk,
            )
        else:
            new_cache = None
            q = q.reshape(B, S, KV, G, hd)
            out = _maybe_pallas_attention(
                cfg, q, k, v, causal=causal, window=window, q_offset=0, kv_len=None
            )

    out = out.reshape(B, S, H, -1)
    y = jnp.einsum("bshk,hkd->bsd", out, p["w_o"].astype(ct))
    if ctx is not None:
        y = shard_hint(y, ctx, ("dp", None, None))
    return y, new_cache


def init_kv_cache(cfg, batch: int, max_len: int, window: int = 0) -> Params:
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    size = min(window, max_len) if window > 0 else max_len
    return {
        "k": jnp.zeros((batch, size, KV, hd), cfg.compute_dtype),
        "v": jnp.zeros((batch, size, KV, hd), cfg.compute_dtype),
        "length": jnp.zeros((batch,), jnp.int32),  # total tokens seen
    }


def _update_kv_cache(cache, k_new, v_new, positions, window, aligned=False):
    """Insert new keys into the (possibly ring) cache buffer."""
    B, S_new = k_new.shape[0], k_new.shape[1]
    size = cache["k"].shape[1]
    length = cache["length"]  # (B,)
    if aligned and window == 0:
        # aligned continuous batching: one write slot for the whole batch.
        # dynamic-update-slice (vs ragged scatter) partitions cleanly when
        # the cache is sequence-sharded; the ragged variant forces SPMD to
        # rematerialize the full stacked cache every layer.
        slot = length[0]
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
        new_len = length + S_new
        new_cache = {"k": k, "v": v, "length": new_len}
        return k, v, new_cache, new_len, length, True
    # ring-buffer write positions (for non-ring caches length < size always)
    write_pos = (length[:, None] + jnp.arange(S_new)) % size  # (B, S_new)
    bidx = jnp.arange(B)[:, None]
    k = cache["k"].at[bidx, write_pos].set(k_new)
    v = cache["v"].at[bidx, write_pos].set(v_new)
    new_len = length + S_new
    new_cache = {"k": k, "v": v, "length": new_len}
    if window > 0:
        # Ring semantics (decode only): the buffer holds exactly the last
        # `window` tokens; every valid slot is attendable, ordering within
        # the window does not matter for softmax(QK)V.
        kv_len = jnp.minimum(new_len, size)
        q_offset = jnp.zeros_like(new_len)
        return k, v, new_cache, kv_len, q_offset, False
    # Linear cache: slot index == absolute position, so causal masking with
    # q at absolute offset `length` is exact for both prefill and decode.
    return k, v, new_cache, new_len, length, True


def _fill_linear_cache(cache, k, v):
    """Write a prefill's keys and values into slots 0..S-1 of an empty cache."""
    return {
        "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
        "length": jnp.full_like(cache["length"], k.shape[1]),
    }


def _fill_ring_cache(cache, k, v):
    """Fill a ring cache with the last `window` tokens of a prefill."""
    size = cache["k"].shape[1]
    B, S = k.shape[0], k.shape[1]
    W = min(size, S)
    tail_k = k[:, S - W :]
    tail_v = v[:, S - W :]
    # absolute positions of tail: S-W .. S-1; ring slot = pos % size
    pos = (jnp.arange(S - W, S)[None, :] + jnp.zeros((B, 1), jnp.int32)) % size
    bidx = jnp.arange(B)[:, None]
    new_k = cache["k"].at[bidx, pos].set(tail_k)
    new_v = cache["v"].at[bidx, pos].set(tail_v)
    length = jnp.full_like(cache["length"], S)
    return {"k": new_k, "v": new_v, "length": length}


# -- MLA (multi-head latent attention) ------------------------------------------------

def apply_mla(
    cfg,
    p: Params,
    x: jax.Array,
    *,
    positions: jax.Array,
    cache: Params | None = None,
    ctx: Any = None,
) -> tuple[jax.Array, Params | None]:
    """DeepSeek-V2 MLA: low-rank compressed KV with decoupled RoPE keys.

    The latent ``c`` is RMS-normed before its up-projections and the cache
    holds it normed; the rotary is YaRN-scaled when ``cfg.yarn`` says so.
    Without a cache, and in a prefill into an empty cache, keys and values
    are expanded per head and attend causally over the prompt's own
    positions; the prefill then writes ``c`` and ``k_rope`` into the cache
    with one slice.  Decode uses the *absorbed* formulation: scores are
    computed directly in the latent space, so the cache is only
    (kv_lora_rank + rope_dim) wide.
    """
    from repro.models.common import shard_hint

    m = cfg.mla
    ct = cfg.compute_dtype
    H = cfg.num_heads
    B, S, _ = x.shape
    x = x.astype(ct)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5 * yarn_softmax_factor(cfg.yarn)

    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(ct))
    if ctx is not None:
        q = shard_hint(q, ctx, ("dp", None, "tp", None))
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.yarn)

    ckr = x @ p["w_dkv"].astype(ct)  # (B, S, r + rope)
    c, k_rope = jnp.split(ckr, [m.kv_lora_rank], axis=-1)
    c = apply_norm(cfg, p["kv_norm"], c)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta, cfg.yarn)[:, :, 0]

    if cache is None or _fills_empty_cache(ctx):
        # expand keys/values per head (standard formulation)
        k_nope = jnp.einsum("bsr,rhk->bshk", c, p["w_uk"].astype(ct))
        vfull = jnp.einsum("bsr,rhk->bshk", c, p["w_uv"].astype(ct))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_dim))],
            axis=-1,
        )
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = chunked_attention(
            qf[:, :, :, None, :], k, vfull,
            causal=True, chunk=cfg.attention_chunk, scale=scale,
        ).reshape(B, S, H, m.v_head_dim)
        new_cache = None
        if cache is not None:
            with jax.named_scope("kv_write"):
                new_cache = {
                    "c": jax.lax.dynamic_update_slice(cache["c"], c, (0, 0, 0)),
                    "k_rope": jax.lax.dynamic_update_slice(cache["k_rope"], k_rope, (0, 0, 0)),
                    "length": jnp.full_like(cache["length"], S),
                }
    else:
        # absorbed formulation against the latent cache
        length = cache["length"]
        size = cache["c"].shape[1]
        with jax.named_scope("kv_write"):
            write_pos = (length[:, None] + jnp.arange(S)) % size
            bidx = jnp.arange(B)[:, None]
            c_all = cache["c"].at[bidx, write_pos].set(c)
            kr_all = cache["k_rope"].at[bidx, write_pos].set(k_rope)
        new_len = length + S
        new_cache = {"c": c_all, "k_rope": kr_all, "length": new_len}
        with jax.named_scope("absorbed"):
            out = _absorbed_attention(
                cfg, p, q_nope, q_rope, c_all, kr_all, length, new_len, scale
            )

    y = jnp.einsum("bshk,hkd->bsd", out, p["w_o"].astype(ct))
    if ctx is not None:
        y = shard_hint(y, ctx, ("dp", None, None))
    return y, new_cache


def _absorbed_attention(cfg, p, q_nope, q_rope, c_all, kr_all, length, new_len, scale):
    """Queries absorbed into the latent space attend over the cached ``c``
    (scores against ``[c, k_rope]``, values ``c``), then ``w_uv`` lifts the
    result to each head.

    One new position (decode) sums the two score terms rather than
    concatenate the cache, which would copy it.  Several (a prefill under a
    mesh, or into a cache that holds a prefix) go through the tiled core at
    ``attention_chunk``: a whole ``(B, S, H, T)`` score tensor would not fit.
    """
    ct = cfg.compute_dtype
    B, S, H, _ = q_nope.shape
    T, r = c_all.shape[1], c_all.shape[2]
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(ct))
    if S > 1:
        out_lat = chunked_attention(
            jnp.concatenate([q_abs, q_rope], axis=-1)[:, :, None],  # one kv head, H groups
            jnp.concatenate([c_all, kr_all], axis=-1)[:, :, None],
            c_all[:, :, None],
            causal=True, kv_len=new_len, q_offset=length,
            chunk=cfg.attention_chunk, scale=scale,
        ).reshape(B, S, H, r)
    else:
        s = jnp.einsum("bshr,btr->bsht", q_abs, c_all, preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bshp,btp->bsht", q_rope, kr_all,
                           preferred_element_type=jnp.float32)
        q_pos = length[:, None] + jnp.arange(S)                  # (B, S)
        k_pos = jnp.arange(T)
        mask = (k_pos[None, None, :] < new_len[:, None, None]) & (
            k_pos[None, None, :] <= q_pos[:, :, None])           # (B, S, T)
        s = jnp.where(mask[:, :, None, :], s * scale, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out_lat = jnp.einsum(
            "bsht,btr->bshr", w.astype(ct), c_all, preferred_element_type=jnp.float32
        ).astype(ct)
    return jnp.einsum("bshr,rhk->bshk", out_lat, p["w_uv"].astype(ct))


def init_mla_cache(cfg, batch: int, max_len: int) -> Params:
    m = cfg.mla
    return {
        "c": jnp.zeros((batch, max_len, m.kv_lora_rank), cfg.compute_dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), cfg.compute_dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }
