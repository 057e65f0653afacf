"""Height shardings for the HALP spatial executor (``repro.spatial``): the
image batch is split along its height over one mesh axis and the parameters
are replicated, for the equal split and the capacity-weighted padded layout
alike.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["spatial_shardings", "weighted_spatial_inputs"]


def spatial_shardings(mesh: Mesh, *, axis: str = "sp"):
    """(activation, param) NamedShardings for the HALP spatial executor:
    activations [B, H(or n*Hmax padded), W, C] height-sharded over ``axis``,
    params replicated.  Works for both the equal split and the
    capacity-weighted padded layout (which keeps equal per-device blocks)."""
    return (
        NamedSharding(mesh, P(None, axis, None, None)),
        NamedSharding(mesh, P()),
    )


def weighted_spatial_inputs(x, plan_or_heights, mesh: Mesh, *, axis: str = "sp",
                            align: int = 1):
    """Lay a global image batch out for the capacity-weighted spatial executor.

    ``plan_or_heights`` is either an N-way ``plan_even(ratios=...)`` HALPPlan
    (its first-layer row shares become the shard heights, re-quantised to
    ``align`` -- pass ``spatial_alignment(net)``) or an explicit height tuple.
    Returns ``(x_padded_sharded, heights)``: the padded equal-block layout the
    weighted ``conv2d_spatial(heights=...)`` ops expect, placed with the
    height sharding over ``axis``."""
    from ..spatial.halo import plan_shard_heights, to_padded_shards

    if hasattr(plan_or_heights, "parts"):
        heights = plan_shard_heights(plan_or_heights, align)
    else:
        heights = tuple(int(h) for h in plan_or_heights)
    n = mesh.shape[axis]
    if len(heights) != n:
        raise ValueError(f"{len(heights)} shard heights for a {n}-way {axis!r} axis")
    xp = to_padded_shards(x, heights)
    act_sh, _ = spatial_shardings(mesh, axis=axis)
    return jax.device_put(xp, act_sh), heights
