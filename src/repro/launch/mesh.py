"""Production mesh construction.

``make_production_mesh`` is a *function* (not a module-level constant) so
importing this module never touches JAX device state — required because the
dry-run pins ``xla_force_host_platform_device_count`` before first init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 4):
    """Small mesh for multi-device CPU tests (needs forced host devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple[str, ...]:
    """The pure data-parallel axes of a mesh (pod is outer DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
