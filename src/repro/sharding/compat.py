"""The two sharding calls this repo makes, with their options pinned once.

``shard_map`` programs run unchecked (``check_vma=False``: the serving
bodies mix replicated and sharded operands on purpose) and meshes use
``Auto`` axes, so every call site agrees on both.
"""

from __future__ import annotations

from typing import Sequence

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check off by default."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )
