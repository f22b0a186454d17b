"""Production mesh construction.

Single pod: 16 x 16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod: 2 x 16 x 16 = 512 chips, axes (pod, data, model) — the "pod"
axis is the slowest (DCN-connected) dimension and carries only
data-parallel traffic (gradient all-reduce), never TP collectives.

A FUNCTION (not a module constant) so importing never touches jax device
state: the dry-run sets XLA_FLAGS host-device-count before first init;
smoke tests see the single real CPU device.
"""
from __future__ import annotations

import jax


def make_mesh_auto(shape: tuple, axes: tuple):
    """jax.make_mesh with every axis Auto (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh_auto((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
