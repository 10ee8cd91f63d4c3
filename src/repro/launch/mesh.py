"""Production mesh construction.

Single pod: (16, 16) over ("data", "model") = 256 chips.
Multi-pod:  (2, 16, 16) over ("pod", "data", "model") = 512 chips.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  If more host devices exist than the mesh needs (the
dry-run forces 512), the first prod(shape) devices are used.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> jax.sharding.Mesh:
    n = math.prod(shape)
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {n} devices, have {len(devices)} "
            "(the dry-run must set XLA_FLAGS="
            "--xla_force_host_platform_device_count before importing jax)")
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices[:n],
                         axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh: jax.sharding.Mesh) -> tuple:
    """Mesh axes carrying the batch (pod is an outer data axis)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_axis_size(mesh, names) -> int:
    return math.prod(mesh.shape[n] for n in names)
