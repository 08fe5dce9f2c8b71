"""Flash-attention forward kernel (GQA) for TPU, in Pallas.

TPU adaptation of the flash algorithm (the paper under reproduction has no
kernel-level contribution; this kernel serves the serving/long-context
substrate of the framework):

* Grid is ``(B*H, n_q_blocks, n_kv_blocks)``; the last dimension iterates
  **sequentially** per TPU core, so the online-softmax running state
  (max ``m``, denominator ``l``, accumulator ``acc``) lives in VMEM scratch
  and is carried across kv-block steps -- no HBM traffic for the running
  state.
* BlockSpecs tile Q as ``(1, block_q, hd)`` and K/V as ``(1, block_k, hd)``;
  with the default 128x128 blocks and hd<=256, the working set
  (q + k + v + acc + two vectors) stays well under the ~16 MB v5e VMEM
  budget while the 128-wide dims align with the MXU systolic array.  The
  models call it with 512x512 blocks (``models.attention.FLASH_BLOCK``),
  whose f32 scores and probabilities add 2 MB.
* GQA is expressed in the K/V index maps: query head ``h`` reads kv head
  ``h // group_size`` -- no K/V duplication in HBM.
* Causal masking skips fully-masked kv blocks via ``pl.when`` (compute is
  only issued for blocks intersecting the causal triangle), and applies the
  triangle mask on the single diagonal block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    q_ref,     # (1, block_q, hd)   VMEM
    k_ref,     # (1, block_k, hd)   VMEM
    v_ref,     # (1, block_k, hd)   VMEM
    o_ref,     # (1, block_q, hd)   VMEM
    m_ref,     # (block_q, 128)     VMEM scratch (running max, lane-replicated)
    l_ref,     # (block_q, 128)     VMEM scratch (running denom)
    acc_ref,   # (block_q, hd)      VMEM scratch (weighted value accumulator)
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    q_len: int,
    kv_len: int,
    n_kv_blocks: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # a kv block is live unless it is entirely above the causal diagonal
    block_live = jnp.logical_or(
        not causal, ik * block_k <= iq * block_q + (block_q - 1)
    )

    @pl.when(block_live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)

        mask = k_pos < kv_len  # padded kv tail
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                       # (block_q,)
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)            # rescale of old state
        p = jnp.exp(s - m_new[:, None])            # (block_q, block_k)
        p = jnp.where(mask, p, 0.0)

        l_new = l_ref[:, 0] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        denom = l_ref[:, 0]
        denom = jnp.where(denom == 0.0, 1.0, denom)  # fully-masked rows
        o_ref[0, :, :] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_bh(
    q: jax.Array,   # (BH, Sq_pad, hd)
    k: jax.Array,   # (BKV, Skv_pad, hd)
    v: jax.Array,   # (BKV, Skv_pad, hd)
    *,
    group_size: int,
    causal: bool,
    scale: float,
    q_len: int,
    kv_len: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    """Pallas call over flattened (batch*head) leading dims; inputs padded."""
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    n_q = Sq // block_q
    n_k = Skv // block_k

    kernel = functools.partial(
        _fa_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        q_len=q_len,
        kv_len=kv_len,
        n_kv_blocks=n_k,
    )

    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j, g=group_size: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j, g=group_size: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
