"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state, so tests and benches keep their 1-CPU world
unless a caller explicitly builds the mesh (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` first).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the sharding rules place
    arrays with ``with_sharding_constraint``, which refuses the
    ``Explicit`` axes ``jax.make_mesh`` gives by default."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pods: int = 0):
    """Small mesh for unit tests (requires enough local devices)."""
    if pods:
        return _auto_mesh((pods, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def tp_degree(mesh) -> int:
    return mesh.shape["model"]


def dp_degree(mesh) -> int:
    d = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        d *= mesh.shape["pod"]
    return d
