"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else sees the real (single-CPU) device set.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis `Auto`: the sharding code places
    arrays with `with_sharding_constraint` and `NamedSharding`, which
    JAX's default `Explicit` axes reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh adds a leading DCN
    "pod" axis (2 pods = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(*, data: int = 1, model: int = 1):
    """Tiny mesh over the actually-present devices (tests/examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return make_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
