"""Production mesh builders.

A function (not a module-level constant) so importing this module never
touches jax device state. Meshes use Auto axes: the model code places
tensors with ``with_sharding_constraint`` hints (``distributed/axes.py``),
which Explicit axes — ``jax.make_mesh``'s default — reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 2):
    """Small mesh over whatever local devices exist (tests/examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    while n % model:
        model -= 1
    return _auto_mesh((n // model, model), ("data", "model"))
