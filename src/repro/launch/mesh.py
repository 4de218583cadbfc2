"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips
(TPU v5e-256-class). Multi-pod: a leading pod axis, (pod=2, data=16,
model=16) = 512 chips; batch dims shard jointly over ("pod", "data").
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """jax.make_mesh needs exactly prod(shape) devices; when the runtime has
    more (e.g. 512 forced host devices but a 256-chip single-pod mesh), build
    the Mesh from the first prod(shape) devices directly.

    Every axis is ``Auto``: the models place activations with
    ``with_sharding_constraint``, which accepts only Auto axes (make_mesh
    defaults to Explicit)."""
    n = int(np.prod(shape))
    axis_types = (AxisType.Auto,) * len(axes)
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)
    if len(devices) < n:
        raise ValueError(f"need {n} devices for mesh {tuple(shape)}, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(tuple(shape))
    return jax.sharding.Mesh(arr, tuple(axes), axis_types=axis_types)


def describe(mesh) -> dict:
    return {
        "axes": dict(mesh.shape),
        "devices": int(np.prod(list(mesh.shape.values()))),
        "platform": jax.devices()[0].platform,
    }
