"""Mesh helpers shared by the model code and the sharded service."""
from __future__ import annotations

import jax
import numpy as np


def get_mesh():
    """Abstract mesh of the enclosing ``jax.set_mesh`` context, or None."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with explicit Auto axis types.

    ``devices`` builds the mesh over an explicit device subset (e.g. a
    1-shard or 4-shard submesh of an 8-device host) — ``jax.make_mesh``
    lays out every device by topology, so submeshes construct ``Mesh``
    directly."""
    if devices is not None:
        return jax.sharding.Mesh(
            np.asarray(devices).reshape(axis_shapes), axis_names)
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
