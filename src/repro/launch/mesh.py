"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device;
only `dryrun.py` forces 512 host devices.

Every mesh is built with ``Auto`` axis types (`auto_axes`): the sharding
rules and the shard_map pipeline are written for XLA's sharding propagation,
not for sharding-in-types (`jax.make_mesh` defaults to ``Explicit``).
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def auto_axes(n: int) -> Tuple[AxisType, ...]:
    """``axis_types`` for an n-axis `jax.make_mesh` under propagation."""
    return (AxisType.Auto,) * n


def make_process_mesh(shape: Tuple[int, ...], axis_names: Sequence[str]) -> Mesh:
    """Row-major mesh over the raw global device list (multi-controller path).

    `jax.make_mesh` may permute devices for ICI locality; multi-process
    data loading and checkpoint shard ownership assume the device grid is
    exactly `jax.devices()` reshaped row-major, so process slabs line up
    with contiguous (pod, stage, data) slabs. Built through the raw `Mesh`
    constructor to pin that order.
    """
    import numpy as np

    devices = np.array(jax.devices())
    n = 1
    for s in shape:
        n *= s
    if devices.size != n:
        raise ValueError(
            f"{devices.size} global devices do not fill mesh shape {shape}"
        )
    return Mesh(devices.reshape(tuple(shape)), tuple(axis_names),
                axis_types=auto_axes(len(axis_names)))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=auto_axes(len(axes)))


def make_smoke_mesh() -> Mesh:
    """Single-device mesh with the production axis names (for tests)."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=auto_axes(2))


def mesh_shape_dict(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
