"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
state.  Single pod: 16x16 = 256 chips (v5e pod).  Multi-pod: 2 pods = 512
chips with a dedicated "pod" axis (data-parallel across the pod boundary —
the only traffic crossing DCN is the gradient all-reduce).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding rules place
    activations with ``with_sharding_constraint``, which Explicit axes
    (the default) reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))
