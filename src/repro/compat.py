"""Mesh and shard_map construction with this repo's defaults.

``jax.make_mesh`` makes Explicit axes by default; every mesh here uses Auto
axes (sharding propagated by the compiler), and every ``shard_map`` skips
the varying-manual-axes check.  Route mesh/shard_map construction through
here instead of calling jax directly.
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], names: Sequence[str]) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(names)),
    )


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check`` mapped onto ``check_vma``."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )
