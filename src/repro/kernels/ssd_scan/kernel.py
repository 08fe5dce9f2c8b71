"""Mamba-2 SSD (state-space duality) chunked-scan kernel for TPU, in Pallas.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the sequence is cut
into chunks of length Q; within a chunk the dual *quadratic* form runs on the
MXU (two (Q,Q)/(Q,P) matmuls -- exactly the unit the systolic array wants),
while the inter-chunk recurrence carries a single (P, N) state in VMEM
scratch across the sequential chunk dimension of the grid.

Grid: ``(B*H, n_chunks)`` -- the chunk axis is innermost, so per (batch,
head) stream the state scratch persists step to step and never touches HBM.
BlockSpecs hand the kernel one chunk of x/a/b/c at a time:

    x (1, Q, P), a (1, 1, Q), b (1, Q, N), c (1, Q, N)   ->   y (1, Q, P)

With Q=128, P=64, N=128 the working set is ~200 kB -- far under VMEM; Q and
N are MXU-aligned at 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,        # (1, Q, P)
    a_ref,        # (1, 1, Q)
    b_ref,        # (1, Q, N)
    c_ref,        # (1, Q, N)
    s0_ref,       # (1, P, N)  initial state
    y_ref,        # (1, Q, P)  out
    sout_ref,     # (1, P, N)  out: final state
    state_ref,    # (P, N) f32 VMEM scratch, carried across chunks
    *,
    n_chunks: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)     # (Q, P)
    a = a_ref[0].astype(jnp.float32)     # (1, Q) log-decays along the lanes
    b = b_ref[0].astype(jnp.float32)     # (Q, N)
    c = c_ref[0].astype(jnp.float32)     # (Q, N)
    Q = x.shape[0]

    # Mosaic lowers neither cumsum nor scalar reads at a dynamic index, so
    # the prefix sums come from masks: with a_low[i, k] = a[k] for k <= i,
    #   a_cum[i]          = sum_k a_low[i, k]                (lane reduction)
    #   seg[i, j] (j < i) = sum_{j < k <= i} a[k] = (a_low @ [k > j])[i, j]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = row >= col
    a_low = jnp.where(tri, a, 0.0)                   # (Q, Q)
    a_cum = jnp.sum(a_low, axis=1, keepdims=True)    # (Q, 1)
    a_tot = jnp.sum(a, axis=1, keepdims=True)        # (1, 1)

    # -- intra-chunk: dual quadratic form on the MXU -------------------------
    # L[i, j] = exp(sum a[j+1..i]) for j <= i else 0
    seg = jax.lax.dot_general(
        a_low, (row > col).astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    L = jnp.where(tri, jnp.exp(seg), 0.0)            # (Q, Q)
    s = jax.lax.dot_general(                         # c @ b^T  (Q, Q)
        c, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_diag = jax.lax.dot_general(                    # (s*L) @ x  (Q, P)
        s * L, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # -- carried-in state contribution ---------------------------------------
    state = state_ref[...]                           # (P, N)
    c_decay = c * jnp.exp(a_cum)                     # (Q, N)
    y_off = jax.lax.dot_general(                     # c_decay @ state^T (Q, P)
        c_decay, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    y_ref[0, :, :] = (y_diag + y_off).astype(y_ref.dtype)

    # -- state update for the next chunk --------------------------------------
    decay_to_end = jnp.exp(a_tot - a_cum)            # (Q, 1)
    bx = jax.lax.dot_general(                        # x^T @ (b*decay) (P, N)
        x * decay_to_end, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    state_ref[...] = state * jnp.exp(a_tot) + bx

    @pl.when(j == n_chunks - 1)
    def _final():
        sout_ref[0, :, :] = state_ref[...]


def ssd_scan_bh(
    x: jax.Array,    # (BH, S_pad, P)  pre-multiplied by dt
    a: jax.Array,    # (BH, 1, S_pad)  log-decay per step
    b: jax.Array,    # (BH, S_pad, N)
    c: jax.Array,    # (BH, S_pad, N)
    s0: jax.Array,   # (BH, P, N)      initial state (f32)
    *,
    chunk: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    BH, S, P = x.shape
    N = b.shape[-1]
    n_chunks = S // chunk

    kernel = functools.partial(_ssd_kernel, n_chunks=n_chunks)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, chunk, N), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, P, N), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, P, N), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, a, b, c, s0)
    return y, s_final
