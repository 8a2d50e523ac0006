"""Mesh construction for the installed JAX (0.9).

`make_mesh` is the one place meshes are built: ``jax.make_mesh`` now
defaults every axis to ``AxisType.Explicit``, which types shardings into
every array and rejects the compiler-propagated (``Auto``) programs this
code is written as.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler).  `devices` defaults to all visible devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
