"""Weights drawn from the seed, the same bits for the program and the reference.

Each leaf of a layer is a function of (seed, leaf name, layer index) alone:
``leaf("attn/w_q", 7, ...)`` gives layer 7's query projection whether it is
drawn alone (the reference, one layer at a time) or as one row of a stacked
layer group (the program's parameter tree, drawn in one jitted call).  Matrix
entries are uniform with the fan-in standard deviation (the fan-in is axis 0
of a projection, axis 1 of a routed-expert stack ``moe/*`` of shape
``(experts, in, out)``); every leaf named ``scale`` is ones.  The arithmetic after
the random bits is one exact affine map and one rounding multiply, so no
compiler fusion can change a bit.
"""

from __future__ import annotations

import math
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

ONES = {"scale", "norm_scale", "beta_attn", "beta_ssm", "d_skip"}
ZEROS = {"conv_b"}
FAN_IN_FIRST = {"w_q", "w_k", "w_v", "w_in", "w_gate", "w_up", "w_down", "w_out",
                "w_dkv", "w_uk", "w_uv", "router"}


def _std(name: str, shape: tuple[int, ...]) -> float:
    path = name.split("/")
    last = path[-1]
    if last == "embed":
        return 0.02
    if last == "unembed":
        return shape[1] ** -0.5
    if path[0] == "moe" and len(shape) == 3:
        return shape[1] ** -0.5               # (E, in, out): one matrix per expert
    if last in FAN_IN_FIRST:
        return shape[0] ** -0.5
    if last == "w_o":                         # (H, hd, d)
        return math.prod(shape[:-1]) ** -0.5
    if last == "conv_w":                      # (C, K) depthwise
        return shape[-1] ** -0.5
    raise KeyError(f"no weight rule for leaf {name!r}")


def leaf(key: jax.Array, name: str, layer, shape: tuple[int, ...], dtype) -> jax.Array:
    """The value of leaf ``name`` (path inside a layer, e.g. ``attn/w_q``)
    of layer ``layer`` (-1 outside the layer stack) under the run's ``key``
    (``bench.generate.jax_key(seed)``); traceable, ``layer`` too."""
    last = name.split("/")[-1]
    if last in ONES:
        return jnp.ones(shape, dtype)
    if last in ZEROS:
        return jnp.zeros(shape, dtype)
    if last == "a_log":                       # mamba2: A = -[1 .. 16]
        return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=jnp.float32)).astype(dtype)
    if last == "dt_bias":                     # softplus^-1(0.01)
        return jnp.full(shape, math.log(math.expm1(0.01)), jnp.float32).astype(dtype)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()))
    key = jax.random.fold_in(key, layer + 1)
    bits = jax.random.bits(key, shape, jnp.uint32)
    one_two = jax.lax.bitcast_convert_type((bits >> 9) | np.uint32(0x3F800000), jnp.float32)
    u = one_two * 2.0 - 3.0                   # exact: uniform on [-1, 1)
    return (u * np.float32(_std(name, shape) * math.sqrt(3.0))).astype(dtype)


def _path(path) -> list[str]:
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def program_params(key: jax.Array, shapes: Any, group_layers: dict[str, list[int]], dtype) -> Any:
    """A parameter tree shaped like ``shapes`` (the program's layout).

    ``group_layers`` maps each stacked layer group to the global indices of
    its layers; every other top-level entry is drawn with layer -1.
    Traceable: call it inside one ``jax.jit`` with the tree's shardings and
    the key as an argument, so that every seed runs the same program.
    """

    def one(path, s):
        keys = _path(path)
        if keys[0] in group_layers:
            name = "/".join(keys[1:])
            layers = group_layers[keys[0]]
            return jnp.stack([leaf(key, name, i, s.shape[1:], dtype) for i in layers])
        return leaf(key, "/".join(keys), -1, s.shape, dtype)

    return jax.tree_util.tree_map_with_path(one, shapes)
