"""Production mesh definitions (TPU v5e).

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the `pod` axis
carries data parallelism across the DCN/ICI pod boundary.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """A device mesh whose axes are all ``Auto``: placements come from
    the operands' ``NamedSharding``s and GSPMD propagates them through
    each jitted step (``jax.make_mesh`` otherwise defaults to
    ``Explicit`` axes, which type-check every op's sharding)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Axes carrying batch data parallelism."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
