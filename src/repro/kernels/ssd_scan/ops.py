"""Jit'd public wrapper for the SSD chunked-scan Pallas kernel.

Accepts the model-layer layout (B, S, H, ...), flattens (batch, head) for
the kernel, pads S to a chunk multiple (zero padding is algebraically inert:
``a=0`` means decay 1 and ``x=b=0`` contribute nothing to state or output),
and returns both the sequence output and the final state for decode handoff.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,    # (B, S, H, P)   pre-multiplied by dt
    a: jax.Array,    # (B, S, H)      log-decay per step (negative)
    b: jax.Array,    # (B, S, H, N)
    c: jax.Array,    # (B, S, H, N)
    initial_state: jax.Array | None = None,  # (B, H, P, N)
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N) float32)."""
    from repro.kernels.ssd_scan.kernel import ssd_scan_bh

    B, S, H, P = x.shape
    N = b.shape[-1]
    if interpret is None:
        interpret = interpret_mode()
    Q = min(chunk, max(8, 1 << (S - 1).bit_length()))
    pad = (-S) % Q

    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    af = a.transpose(0, 2, 1).reshape(B * H, 1, S)
    bf = b.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    cf = c.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, pad), (0, 0)))
        af = jnp.pad(af, ((0, 0), (0, 0), (0, pad)))
        bf = jnp.pad(bf, ((0, 0), (0, pad), (0, 0)))
        cf = jnp.pad(cf, ((0, 0), (0, pad), (0, 0)))

    s0 = (
        initial_state.reshape(B * H, P, N).astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((B * H, P, N), jnp.float32)
    )

    y, s_final = ssd_scan_bh(xf, af, bf, cf, s0, chunk=Q, interpret=interpret)
    y = y[:, :S].reshape(B, H, S, P).transpose(0, 2, 1, 3)
    return y, s_final.reshape(B, H, P, N)
