"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state. Single pod: 16x16 = 256 chips (data, model). Two pods:
(2, 16, 16) = 512 chips (pod, data, model); the `pod` axis is pure data
parallelism across the DCN/inter-pod boundary.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with production axis names (CPU tests)."""
    return _make_mesh((1, 1), ("data", "model"))
