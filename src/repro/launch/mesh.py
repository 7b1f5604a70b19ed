"""Production meshes.  A FUNCTION, not a module constant, so importing this
module never touches jax device state (the dry-run must set XLA_FLAGS
before the first jax call)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """jax.make_mesh with every axis Auto (jax defaults to Explicit): the
    serving and solver code place arrays with shardings and shard_map,
    not with explicit-axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def parse_mesh(spec: str) -> jax.sharding.Mesh:
    """CLI mesh spec -> Mesh: 'DxM' = (data, model), 'PxDxM' adds pods.

    '2x4' is 2-way data parallel (slot sharding in serving) x 4-way model
    parallel (solver vocab sharding); CPU testing reaches D*M devices via
    --xla_force_host_platform_device_count (launch/serve.py
    --host-devices).
    """
    try:
        dims = tuple(int(s) for s in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}; want e.g. '2x4'") from None
    if len(dims) == 2:
        return make_mesh(dims, ("data", "model"))
    if len(dims) == 3:
        return make_mesh(dims, ("pod", "data", "model"))
    raise ValueError(f"mesh spec {spec!r} must have 2 or 3 dims")


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh: jax.sharding.Mesh):
    """The data-parallel axes present in this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
