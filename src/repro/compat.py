"""Mesh construction shared by the sharded paths.

``jax.make_mesh`` defaults to explicit-sharding axis types; every mesh in
this repo is built for ``shard_map`` regions and sharding constraints
under the automatic (GSPMD) mode, so it asks for ``AxisType.Auto`` axes.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axis types on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
