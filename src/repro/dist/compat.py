"""Mesh construction with explicit Auto axis types.

Everything in this repo that builds a mesh goes through these two helpers
(launch/mesh.py, tests/test_dist.py, the sharded GNN runtime), so every
mesh carries the same ``AxisType.Auto`` axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Concrete device mesh over the first ``prod(shape)`` devices."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Device-free AbstractMesh (sharding-rule tests / dry planning)."""
    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
