"""Production meshes. Import must never touch jax device state — meshes are
built by functions only (the dry-run sets XLA_FLAGS before first jax init)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = (data, model) — 256 chips (v5e pod).
    Multi-pod: (2, 16, 16) = (pod, data, model) — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(devices: int = 8, model: int = 2):
    """Small mesh for CPU integration tests (requires the host-device flag)."""
    return jax.make_mesh((devices // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mp_axis(mesh) -> str:
    return "model"
