"""EfficientNet (Tan & Le, arXiv:1905.11946) -- efficientnet-b7
(width_mult=2.0, depth_mult=3.1, img_res=600).

MBConv blocks with squeeze-excitation.  Every operator except the SE global
pool is sliding-window, so the paper's partitioning applies layer-wise; the SE
pool is the one cross-segment synchronisation point (noted in DESIGN.md).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import Params, conv_params, dense_params, keygen
from .layers import batchnorm_inference, conv2d, dense, global_avg_pool, silu

__all__ = ["EfficientNetConfig", "init", "apply"]

# B0 baseline: (expand, channels, repeats, stride, kernel)
B0_STAGES = (
    (1, 16, 1, 1, 3),
    (6, 24, 2, 2, 3),
    (6, 40, 2, 2, 5),
    (6, 80, 3, 2, 3),
    (6, 112, 3, 1, 5),
    (6, 192, 4, 2, 5),
    (6, 320, 1, 1, 3),
)


@dataclass(frozen=True)
class EfficientNetConfig:
    name: str = "efficientnet-b7"
    img_res: int = 600
    width_mult: float = 2.0
    depth_mult: float = 3.1
    num_classes: int = 1000
    in_channels: int = 3
    se_ratio: float = 0.25
    stem_ch: int = 32
    head_ch: int = 1280

    def round_ch(self, c: int) -> int:
        c = c * self.width_mult
        new = max(8, int(c + 4) // 8 * 8)
        if new < 0.9 * c:
            new += 8
        return new

    def round_reps(self, r: int) -> int:
        return int(math.ceil(self.depth_mult * r))

    def stages(self):
        return [
            (e, self.round_ch(c), self.round_reps(r), s, k) for e, c, r, s, k in B0_STAGES
        ]


def _bn_params(c, dtype):
    return {
        "scale": jnp.ones((c,), dtype),
        "b": jnp.zeros((c,), dtype),
        "mean": jnp.zeros((c,), dtype),
        "var": jnp.ones((c,), dtype),
    }


def _mbconv_init(key, c_in, c_out, expand, k, se_ratio, dtype):
    ks = keygen(key)
    c_mid = c_in * expand
    p: Params = {}
    if expand != 1:
        p["expand"] = conv_params(next(ks), 1, c_in, c_mid, bias=False, dtype=dtype)
        p["bn0"] = _bn_params(c_mid, dtype)
    p["dw"] = conv_params(next(ks), k, c_mid, c_mid, bias=False, groups=c_mid, dtype=dtype)
    p["bn1"] = _bn_params(c_mid, dtype)
    c_se = max(1, int(c_in * se_ratio))
    p["se_reduce"] = dense_params(next(ks), c_mid, c_se, dtype=dtype)
    p["se_expand"] = dense_params(next(ks), c_se, c_mid, dtype=dtype)
    p["project"] = conv_params(next(ks), 1, c_mid, c_out, bias=False, dtype=dtype)
    p["bn2"] = _bn_params(c_out, dtype)
    return p


def _mbconv_apply(p, x, stride, k):
    c_in = x.shape[-1]
    h = x
    if "expand" in p:
        h = silu(batchnorm_inference(conv2d(h, p["expand"], padding="VALID"), p["bn0"]))
    pad = (k - 1) // 2
    h = silu(batchnorm_inference(
        conv2d(h, p["dw"], stride=stride, padding=pad, groups=h.shape[-1]), p["bn1"]))
    # squeeze-excitation (the global pool is the cross-segment sync point)
    se = global_avg_pool(h)
    se = jax.nn.sigmoid(dense(silu(dense(se, p["se_reduce"])), p["se_expand"]))
    h = h * se[:, None, None, :]
    h = batchnorm_inference(conv2d(h, p["project"], padding="VALID"), p["bn2"])
    if stride == 1 and h.shape[-1] == c_in:
        h = h + x
    return h


def init(key, cfg: EfficientNetConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    stem_c = cfg.round_ch(cfg.stem_ch)
    p: Params = {
        "stem": conv_params(next(ks), 3, cfg.in_channels, stem_c, bias=False, dtype=dtype),
        "stem_bn": _bn_params(stem_c, dtype),
        "blocks": [],
    }
    c_in = stem_c
    blocks = []
    # static metadata (stride/kernel) lives in block_meta(cfg); params are arrays
    for e, c_out, reps, s, k in cfg.stages():
        for r in range(reps):
            blocks.append(_mbconv_init(next(ks), c_in, c_out, e, k, cfg.se_ratio, dtype))
            c_in = c_out
    p["blocks"] = blocks
    head_c = cfg.round_ch(cfg.head_ch)
    p["head_conv"] = conv_params(next(ks), 1, c_in, head_c, bias=False, dtype=dtype)
    p["head_bn"] = _bn_params(head_c, dtype)
    p["fc"] = dense_params(next(ks), head_c, cfg.num_classes, dtype=dtype)
    return p


def block_meta(cfg: EfficientNetConfig) -> list[tuple[int, int]]:
    """Static (stride, kernel) per block, aligned with params['blocks']."""
    meta = []
    for e, c_out, reps, s, k in cfg.stages():
        for r in range(reps):
            meta.append((s if r == 0 else 1, k))
    return meta


def apply(params: Params, cfg: EfficientNetConfig, x: jax.Array) -> jax.Array:
    x = silu(batchnorm_inference(
        conv2d(x, params["stem"], stride=2, padding=1), params["stem_bn"]))
    for p_b, (s, k) in zip(params["blocks"], block_meta(cfg)):
        x = _mbconv_apply(p_b, x, s, k)
    x = silu(batchnorm_inference(
        conv2d(x, params["head_conv"], padding="VALID"), params["head_bn"]))
    return dense(global_avg_pool(x), params["fc"])
