"""shard_map with the varying-axes check off, and the static axis size.

JAX enforces static "varying-over-mesh-axes" (vma) inference; outputs
produced by all_gather are mathematically replicated but the checker can't
prove it, so every shard_map in this repo goes through the wrapper here,
which disables the check.
"""

import jax
from jax.lax import axis_size


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


__all__ = ["shard_map", "axis_size"]
