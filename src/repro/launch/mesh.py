"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state; the dry-run sets the fake-device XLA flag before
calling them.

Every axis is ``Auto``: the model code places arrays with
``with_sharding_constraint``, which may only name Auto axes, while
``jax.make_mesh`` makes Explicit ones unless told otherwise.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(
    shape: tuple[int, ...], axes: tuple[str, ...], devices: Sequence | None = None
) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_data_mesh(devices: Sequence | None = None) -> jax.sharding.Mesh:
    """``(data=n, model=1)`` over ``devices`` (default: every device)."""
    devices = list(devices if devices is not None else jax.devices())
    return make_mesh((len(devices), 1), ("data", "model"), devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """v5e-256 pod mesh: (data=16, model=16); two pods add a 'pod' DP axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
