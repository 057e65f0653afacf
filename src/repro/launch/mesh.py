"""Device summary and the spatial mesh.  Defined as FUNCTIONS so importing
this module never touches jax device state: a test may force host devices
before JAX initialises."""
from __future__ import annotations

import jax

__all__ = ["device_summary", "make_spatial_mesh"]


def device_summary() -> dict:
    """The backend JAX actually brought up: platform, device kind, count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_spatial_mesh(n: int | None = None, *, axis: str = "sp"):
    """1-D mesh over the image-height axis for the HALP spatial executor
    (``repro.spatial``): ``n`` devices (default: all local devices) along a
    single ``"sp"`` axis.  Capacity-weighted deployments keep this equal-block
    mesh and encode the skew in the padded shard layout
    (``repro.spatial.halo.shard_heights``)."""
    n = n or len(jax.devices())
    return jax.make_mesh((n,), (axis,))
