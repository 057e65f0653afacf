"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run forces 512 host devices *before* any
jax initialisation; tests and benches see the default single device)."""
from __future__ import annotations

import jax

__all__ = [
    "device_summary",
    "make_production_mesh",
    "make_spatial_mesh",
    "mesh_axes",
    "dp_axes",
    "fsdp_axes",
]


def device_summary() -> dict:
    """The backend JAX actually brought up: platform, device kind, count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_spatial_mesh(n: int | None = None, *, axis: str = "sp"):
    """1-D mesh over the image-height axis for the HALP spatial executor
    (``repro.spatial``): ``n`` devices (default: all local devices) along a
    single ``"sp"`` axis.  Capacity-weighted deployments keep this equal-block
    mesh and encode the skew in the padded shard layout
    (``repro.spatial.halo.shard_heights``)."""
    n = n or len(jax.devices())
    return jax.make_mesh((n,), (axis,))


def mesh_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def dp_axes(mesh):
    """Axes carrying data parallelism (batch sharding)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh):
    """Axes over which large models additionally shard parameters (ZeRO-3)."""
    return dp_axes(mesh)
