"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The single-pod mesh is 16x16 = 256 chips; the
multi-pod mesh is 2 pods x 256 = 512 chips with DP extended over the `pod`
axis (only gradient all-reduce crosses the pod/DCN boundary). Every axis is
`AxisType.Auto`: shardings propagate from the annotations GSPMD sees.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mini_mesh(devices: int = 8, model: int = 2):
    """Small host mesh for CI-style sharded tests (e.g. 8 CPU devices)."""
    data = devices // model
    return _make_mesh((data, model), ("data", "model"))


def mesh_by_name(name: str):
    if name == "single":
        return make_production_mesh(multi_pod=False)
    if name == "multi":
        return make_production_mesh(multi_pod=True)
    if name.startswith("mini"):
        n = len(jax.devices())
        model = 2 if n % 2 == 0 else 1
        return make_mini_mesh(n, model)
    raise ValueError(f"unknown mesh {name!r}")
