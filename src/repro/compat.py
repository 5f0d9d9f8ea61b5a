"""Shared spellings of the jax sharding APIs this repo uses.

``make_mesh`` builds meshes with every axis in Auto mode, ``shard_map``
turns off the replication (vma) check, and ``manual_axes`` reports the
mesh axes that are Manual in the current trace.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` without replication checking; ``axis_names``
    restricts manual mode to those axes (the rest stay automatic)."""
    kw = {"check_vma": False}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def manual_axes() -> set[str]:
    """Mesh axes that are Manual in the current trace (inside shard_map)."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names:
        return set()
    return {n for n, t in zip(am.axis_names, am.axis_types)
            if t == AxisType.Manual}
