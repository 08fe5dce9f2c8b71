"""Tensor-fingerprint kernel for TPU, in Pallas.

Content-addressed keys are the paper's scheduler-compatibility mechanism
(task key = hash of fn+args; the proxy caches the target hash so schedulers
never resolve it).  For multi-GB train-state shards, computing that token is
a pure memory-bandwidth problem -- ideal kernel shape: stream HBM blocks
through VMEM once, keep a (8, 128) uint32 accumulator in scratch (one
native VREG tile), mix each block in with integer multiply/xor on the VPU.

Grid: ``(n_blocks,)`` sequential; BlockSpec hands one (8, 128) uint32 tile
per step.  The fold to 64 bits happens on the final step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fingerprint.ref import M1, PHI, SEED, _fold


def _fp_kernel(x_ref, out_ref, acc_ref, *, n_blocks: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        iota = (
            jax.lax.broadcasted_iota(jnp.uint32, (8, 128), 0) * 128
            + jax.lax.broadcasted_iota(jnp.uint32, (8, 128), 1)
        )
        acc_ref[...] = SEED ^ (iota * PHI)

    salt = (i + 1).astype(jnp.uint32) * PHI
    acc_ref[...] = (acc_ref[...] * M1) ^ (x_ref[0] + salt)

    @pl.when(i == n_blocks - 1)
    def _final():
        out_ref[0, :, :] = acc_ref[...]


def fingerprint_blocks(blocks: jax.Array, *, interpret: bool) -> jax.Array:
    """blocks: (n_blocks, 8, 128) uint32 -> folded (2,) uint32 token."""
    nb = blocks.shape[0]
    kernel = functools.partial(_fp_kernel, n_blocks=nb)
    acc = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 8, 128), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 8, 128), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.uint32)],
        interpret=interpret,
    )(blocks)
    return _fold(acc[0])
