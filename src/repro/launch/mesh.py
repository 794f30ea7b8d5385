"""Production mesh construction (assignment-mandated shape).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax init.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types: the model places arrays by
    sharding constraints and lets XLA propagate the rest."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (fake) host devices exist (tests/benches)."""
    return make_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh: jax.sharding.Mesh):
    return tuple(zip(mesh.axis_names, mesh.devices.shape))
