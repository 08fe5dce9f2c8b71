"""Jit'd public wrapper for the fingerprint kernel: arbitrary arrays in,
64-bit content token out."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.fingerprint.ref import BLOCK_BYTES


@functools.partial(jax.jit, static_argnames=("interpret",))
def fingerprint(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Content fingerprint of any array. Returns (2,) uint32 (64-bit token)."""
    from repro.kernels.fingerprint.kernel import fingerprint_blocks

    if interpret is None:
        interpret = interpret_mode()
    flat = jax.lax.bitcast_convert_type(
        x.reshape(-1), jnp.uint8
    ).reshape(-1) if x.dtype != jnp.uint8 else x.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK_BYTES
    if pad:
        flat = jnp.pad(flat, (0, pad))
    u32 = jax.lax.bitcast_convert_type(flat.reshape(-1, 4), jnp.uint32)
    blocks = u32.reshape(-1, 8, 128)
    return fingerprint_blocks(blocks, interpret=interpret)


def fingerprint_token(x, *, interpret: bool | None = None) -> str:
    """Hex token for store/scheduler keys."""
    import numpy as np

    h = np.asarray(fingerprint(jnp.asarray(x), interpret=interpret))
    return f"{int(h[0]):08x}{int(h[1]):08x}"
