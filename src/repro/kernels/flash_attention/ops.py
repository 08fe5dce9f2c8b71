"""Jit'd public wrapper for the flash-attention Pallas kernel.

Handles layout (B, H, S, hd) <-> kernel layout, GQA head mapping and padding
to block multiples.  ``interpret=True`` executes the kernel body in Python --
the same algorithm that runs compiled on the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_gqa(
    q: jax.Array,   # (B, H, Sq, hd)
    k: jax.Array,   # (B, KV, Skv, hd)
    v: jax.Array,   # (B, KV, Skv, hd)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention with grouped-query heads. Returns (B, H, Sq, hd)."""
    from repro.kernels.flash_attention.kernel import flash_attention_bh

    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    assert H % KV == 0, f"H={H} not a multiple of KV={KV}"
    g = H // KV
    if scale is None:
        scale = hd**-0.5
    if interpret is None:
        interpret = interpret_mode()

    bq = min(block_q, max(8, 1 << (Sq - 1).bit_length()))
    bk = min(block_k, max(8, 1 << (Skv - 1).bit_length()))

    qf = _pad_to(q.reshape(B * H, Sq, hd), 1, bq)
    kf = _pad_to(k.reshape(B * KV, Skv, hd), 1, bk)
    vf = _pad_to(v.reshape(B * KV, Skv, hd), 1, bk)

    out = flash_attention_bh(
        qf, kf, vf,
        group_size=g,
        causal=causal,
        scale=scale,
        q_len=Sq,
        kv_len=Skv,
        block_q=bq,
        block_k=bk,
        interpret=interpret,
    )
    return out[:, :Sq].reshape(B, H, Sq, hd)
