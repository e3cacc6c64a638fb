"""Axis-gated collectives for mesh-aware MoR statistics.

The MoR decision metrics (group amax, Eq. 3 error sums, the Eq. 2
global accept ratio, the stats-vector fractions) are *tensor-global*
quantities. When a quantization event runs inside ``shard_map`` each
device only sees its shard, so every global aggregate must be
allreduced over the sharded mesh axes before any decision consumes it
-- otherwise per-shard recipes silently diverge from the single-device
choice (see docs/sharding.md).

``MoRPolicy.mesh_axes`` names those axes; these helpers are no-ops when
the tuple is empty, so the single-device path is byte-for-byte the
pre-mesh code.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "psum_over", "pmax_over", "global_size", "all_gather_over",
    "shard_map_unchecked",
]


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checks off: MoR bodies produce
    device-invariant stats via explicit psums, which the static
    replication checker cannot see through."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def psum_over(x: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """lax.psum over ``axes`` when non-empty, identity otherwise."""
    return jax.lax.psum(x, tuple(axes)) if axes else x


def pmax_over(x: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """lax.pmax over ``axes`` when non-empty, identity otherwise."""
    return jax.lax.pmax(x, tuple(axes)) if axes else x


def all_gather_over(x: jnp.ndarray, axis: str | None) -> jnp.ndarray:
    """lax.all_gather over ``axis`` when named, else the degenerate
    single-participant stack ``x[None]`` -- so a collective body (e.g.
    the MoR-payload pod psum in :mod:`repro.optim.compress`) lowers
    unchanged on a single-pod mesh or entirely outside shard_map."""
    if axis is None:
        return x[None]
    return jax.lax.all_gather(x, axis)


def global_size(local_size: int, axes: Sequence[str]) -> jnp.ndarray:
    """Global element count of a sharded operand (psum of the local
    count). For a *replicated* operand this over-counts by the axis
    product -- harmless for MoR because every consumer is a ratio of
    two psums (see docs/sharding.md, 'replication safety')."""
    return psum_over(jnp.float32(local_size), axes)
