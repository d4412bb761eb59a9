"""Mesh / shard_map wrappers with the call shape the repo uses."""
from __future__ import annotations

import jax


def make_mesh(shape, axis_names, *, devices=None):
    """jax.make_mesh with every axis ``Auto`` (GSPMD-propagated)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kw)


def shard_map(f, *, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off (collectives here are
    intentionally uneven)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
