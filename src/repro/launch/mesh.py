"""Mesh construction (functions, never module-level state).

Every mesh is built with ``Auto`` axes: the models place activations with
``with_sharding_constraint`` on logical specs (distributed/sharding.py),
which JAX accepts only on ``Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, so nothing should call it except through ``make_mesh``.
"""
from __future__ import annotations

import os

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


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512).

    REPRO_MULTI_SHAPE=2,8,16 overrides the multi-pod shape (used to scope an
    XLA SPMD partitioner abort that is specific to certain subgroup sizes).
    """
    if multi_pod:
        shape = tuple(int(x) for x in os.environ.get(
            "REPRO_MULTI_SHAPE", "2,16,16").split(","))
        return make_mesh(shape, ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh():
    """Whatever this host offers, as a 1D data mesh (tests/examples)."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
