"""The one place the parallel subsystem takes ``shard_map`` from.

Every in-tree user goes through :func:`shard_map` here: ``jax.shard_map``
with the mesh, specs, ``check_vma`` and ``axis_names`` (the subset of mesh
axes mapped manually) passed by keyword, and the two optional ones left
at jax's defaults when not given.
"""
from __future__ import annotations

__all__ = ["shard_map"]


def shard_map(f, mesh, in_specs, out_specs, check_vma=None, axis_names=None):
    import jax
    kwargs = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
