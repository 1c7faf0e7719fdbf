"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
while tests and benches must keep seeing the single real CPU device.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """Every axis Auto: the shardings are placed by the compiler."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, pod: int = 0):
    """Small mesh for CPU tests (requires >= n_data*n_model fake devices)."""
    if pod:
        return _make_mesh((pod, n_data, n_model), ("pod", "data", "model"))
    return _make_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes the batch dimension shards over (pod included when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = 1
    for a in data_axes(mesh):
        out *= sizes[a]
    return out


def model_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("model", 1)
