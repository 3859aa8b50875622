"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — smoke tests see 1 CPU device,
the dry-run sees the 512 forced host devices it sets up before import.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis is an
outer data-parallel ring — cross-pod traffic is gradient all-reduce only
(DCN-friendly), while TP ("model") stays inside a pod's ICI domain.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Single-device mesh for smoke paths that still want `with mesh:`."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_devices(mesh) -> int:
    return int(mesh.devices.size)
