"""ConvNeXt (Liu et al., arXiv:2201.03545) -- convnext-b.

Pure sliding-window operators end to end: the paper's receptive-field
partitioning applies to every layer (the 7x7 depthwise convs are the widest
halos in the assigned pool -- a showcase for the spatial engine).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from .common import Params, conv_params, dense_params, keygen, norm_params, stack_layers
from .layers import conv2d, dense, gelu, layernorm

__all__ = ["ConvNeXtConfig", "init", "apply"]


@dataclass(frozen=True)
class ConvNeXtConfig:
    name: str = "convnext-b"
    img_res: int = 224
    depths: tuple[int, ...] = (3, 3, 27, 3)
    dims: tuple[int, ...] = (128, 256, 512, 1024)
    num_classes: int = 1000
    in_channels: int = 3
    layer_scale: float = 1e-6
    remat: bool = True


def _block_init(key, dim, dtype, layer_scale):
    ks = keygen(key)
    return {
        "dw": conv_params(next(ks), 7, dim, dim, groups=dim, dtype=dtype),
        "ln": norm_params(dim, dtype=dtype),
        "pw1": dense_params(next(ks), dim, 4 * dim, dtype=dtype),
        "pw2": dense_params(next(ks), 4 * dim, dim, dtype=dtype),
        "gamma": layer_scale * jnp.ones((dim,), dtype),
    }


def _block_apply(p, x):
    h = conv2d(x, p["dw"], padding=3, groups=x.shape[-1])
    h = layernorm(h, p["ln"])
    h = dense(gelu(dense(h, p["pw1"])), p["pw2"])
    return x + p["gamma"] * h


def init(key, cfg: ConvNeXtConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    p: Params = {
        "stem": conv_params(next(ks), 4, cfg.in_channels, cfg.dims[0], dtype=dtype),
        "stem_norm": norm_params(cfg.dims[0], dtype=dtype),
        "stages": [],
        "ln": norm_params(cfg.dims[-1], dtype=dtype),
        "head": dense_params(next(ks), cfg.dims[-1], cfg.num_classes, dtype=dtype),
    }
    stages = []
    for si, (depth, dim) in enumerate(zip(cfg.depths, cfg.dims)):
        stage = {
            "blocks": stack_layers(
                lambda k, dim=dim: _block_init(k, dim, dtype, cfg.layer_scale),
                next(ks),
                depth,
            )
        }
        if si + 1 < len(cfg.depths):
            stage["down_norm"] = norm_params(dim, dtype=dtype)
            stage["down"] = conv_params(next(ks), 2, dim, cfg.dims[si + 1], dtype=dtype)
        stages.append(stage)
    p["stages"] = stages
    return p


def apply(params: Params, cfg: ConvNeXtConfig, x: jax.Array) -> jax.Array:
    x = conv2d(x, params["stem"], stride=4, padding="VALID")
    x = layernorm(x, params["stem_norm"])
    for si, stage in enumerate(params["stages"]):
        depth = cfg.depths[si]
        if depth >= 6:

            def body(h, p_l):
                return _block_apply(p_l, h), None

            if cfg.remat:
                body = jax.checkpoint(body, prevent_cse=False)
            x, _ = lax.scan(body, x, stage["blocks"])
        else:
            for li in range(depth):
                p_l = jax.tree_util.tree_map(lambda a: a[li], stage["blocks"])
                x = _block_apply(p_l, x)
        if "down" in stage:
            x = layernorm(x, stage["down_norm"])
            x = conv2d(x, stage["down"], stride=2, padding="VALID")
    x = layernorm(jnp.mean(x, axis=(1, 2)), params["ln"])
    return dense(x, params["head"])
