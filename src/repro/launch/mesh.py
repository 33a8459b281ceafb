"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state.  Single pod = 16x16 = 256 chips (TPU v5e pod slice);
multi-pod = 2x16x16 = 512 chips with a leading "pod" axis (outer data
parallelism across the pod-interconnect).
"""
from __future__ import annotations

import jax


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1x1 mesh for CPU tests of the sharded code path."""
    n = len(jax.devices())
    d = 2 if n % 2 == 0 and n > 1 else 1
    return _make_mesh((n // d, d), ("data", "model"))
