"""Production meshes.

Functions (not module constants) so importing never touches device state.

Single pod:  (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips across DCN

The `pod` axis is pure data parallelism (gradient all-reduce crosses the
inter-pod link once per step); `data` is FSDP within a pod; `model` is
tensor parallel within an ICI-connected slice.
"""
from __future__ import annotations

import jax


def _mesh_kwargs(n):
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over available devices (unit tests / CPU)."""
    return jax.make_mesh((data, model), ("data", "model"), **_mesh_kwargs(2))


def mesh_devices(mesh) -> int:
    import math

    return math.prod(mesh.shape.values())
