"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because smoke tests must see 1
device while the dry-run forces 512 placeholder devices via XLA_FLAGS before
any jax import.  Enter a mesh with `jax.set_mesh(mesh)`.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:    (2, 16, 16) = 512 chips, axes (pod, data, model) — the
    `pod` axis carries only data-parallel gradient traffic."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """Arbitrary mesh with every axis of the Auto axis type."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))
