"""The one import point for jax's sharding APIs.

Every call site in the repo takes ``shard_map``, ``make_mesh`` and
``set_mesh`` from here (skylint rule R4), so the repo's sharding
conventions — Auto axis types on every mesh, ``check_vma`` off by
default — live in exactly one place.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["shard_map", "make_mesh", "set_mesh"]


def shard_map(f, mesh=None, *, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map``; ``mesh=None`` uses the ambient mesh installed
    by :func:`set_mesh`."""
    kwargs = dict(in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if mesh is not None:
        kwargs["mesh"] = mesh
    return jax.shard_map(f, **kwargs)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with explicit Auto axis types."""
    kwargs = {"axis_types": (AxisType.Auto,) * len(tuple(axis_names))}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


set_mesh = jax.sharding.set_mesh
