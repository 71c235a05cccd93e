"""Mesh construction (functions, never module-level state).

Every mesh is built with ``Auto`` axes: the models place activations with
``with_sharding_constraint`` on logical specs (distributed/sharding.py),
which JAX accepts only on ``Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, so nothing should call it except through ``make_mesh``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int):
    return (AxisType.Auto,) * n


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=_auto(len(axis_names)), devices=devices)


def abstract_mesh(axis_sizes, axis_names):
    """Device-free ``AbstractMesh`` with every axis ``Auto``."""
    axis_names = tuple(axis_names)
    return jax.sharding.AbstractMesh(tuple(axis_sizes), axis_names,
                                     axis_types=_auto(len(axis_names)))

