"""Stable-Diffusion-1.5 U-Net (Rombach et al., arXiv:2112.10752) -- unet-sd15.

Latent-space U-Net: ch=320, ch_mult=(1,2,4,4), 2 res blocks per level,
self+cross attention (ctx_dim=768) at downsample factors 1/2/4, timestep
conditioning.  The conv path is sliding-window (paper partitioning applies);
attention levels synchronise spatially (cheap at low res -- DESIGN.md §4).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import Params, conv_params, dense_params, keygen, norm_params
from .dit import timestep_embedding
from .layers import conv2d, dense, gelu, groupnorm, silu

__all__ = ["UNetConfig", "init", "apply"]


@dataclass(frozen=True)
class UNetConfig:
    name: str = "unet-sd15"
    img_res: int = 512
    latent_ch: int = 4
    ch: int = 320
    ch_mult: tuple[int, ...] = (1, 2, 4, 4)
    n_res_blocks: int = 2
    attn_down: tuple[int, ...] = (1, 2, 4)  # downsample factors with attention
    ctx_dim: int = 768
    ctx_len: int = 77
    n_heads: int = 8
    groups: int = 32

    @property
    def latent_res(self) -> int:
        return self.img_res // 8


def _res_init(key, c_in, c_out, temb_dim, dtype):
    ks = keygen(key)
    p = {
        "n1": norm_params(c_in, dtype=dtype),
        "c1": conv_params(next(ks), 3, c_in, c_out, dtype=dtype),
        "temb": dense_params(next(ks), temb_dim, c_out, dtype=dtype),
        "n2": norm_params(c_out, dtype=dtype),
        "c2": conv_params(next(ks), 3, c_out, c_out, dtype=dtype),
    }
    if c_in != c_out:
        p["skip"] = conv_params(next(ks), 1, c_in, c_out, dtype=dtype)
    return p


def _res_apply(p, x, temb, groups):
    h = conv2d(silu(groupnorm(x, p["n1"], groups)), p["c1"], padding=1)
    h = h + dense(silu(temb), p["temb"])[:, None, None, :]
    h = conv2d(silu(groupnorm(h, p["n2"], groups)), p["c2"], padding=1)
    skip = conv2d(x, p["skip"], padding="VALID") if "skip" in p else x
    return skip + h


def _attn_init(key, c, ctx_dim, dtype):
    ks = keygen(key)
    return {
        "norm": norm_params(c, dtype=dtype),
        "proj_in": dense_params(next(ks), c, c, dtype=dtype),
        # self-attention
        "sq": dense_params(next(ks), c, c, bias=False, dtype=dtype),
        "sk": dense_params(next(ks), c, c, bias=False, dtype=dtype),
        "sv": dense_params(next(ks), c, c, bias=False, dtype=dtype),
        "so": dense_params(next(ks), c, c, dtype=dtype),
        "n1": norm_params(c, dtype=dtype),
        # cross-attention to the text context
        "cq": dense_params(next(ks), c, c, bias=False, dtype=dtype),
        "ck": dense_params(next(ks), ctx_dim, c, bias=False, dtype=dtype),
        "cv": dense_params(next(ks), ctx_dim, c, bias=False, dtype=dtype),
        "co": dense_params(next(ks), c, c, dtype=dtype),
        "n2": norm_params(c, dtype=dtype),
        # geglu ffn
        "ff1": dense_params(next(ks), c, 8 * c, dtype=dtype),
        "ff2": dense_params(next(ks), 4 * c, c, dtype=dtype),
        "n3": norm_params(c, dtype=dtype),
        "proj_out": dense_params(next(ks), c, c, dtype=dtype),
    }


def _mha(q, k, v, heads):
    b, n, c = q.shape
    m = k.shape[1]
    q = q.reshape(b, n, heads, c // heads)
    k = k.reshape(b, m, heads, c // heads)
    v = v.reshape(b, m, heads, c // heads)
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) / jnp.sqrt(c / heads)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bhnm,bmhd->bnhd", probs, v).reshape(b, n, c)


def _ln(x, p, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["b"]


def _attn_apply(p, x, ctx, heads, groups):
    b, h, w, c = x.shape
    res = x
    xn = groupnorm(x, p["norm"], groups)
    t = dense(xn.reshape(b, h * w, c), p["proj_in"])
    # self
    tn = _ln(t, p["n1"])
    t = t + dense(_mha(dense(tn, p["sq"]), dense(tn, p["sk"]), dense(tn, p["sv"]), heads), p["so"])
    # cross
    tn = _ln(t, p["n2"])
    t = t + dense(_mha(dense(tn, p["cq"]), dense(ctx, p["ck"]), dense(ctx, p["cv"]), heads), p["co"])
    # geglu
    tn = _ln(t, p["n3"])
    u = dense(tn, p["ff1"])
    a, g = jnp.split(u, 2, axis=-1)
    t = t + dense(a * gelu(g), p["ff2"])
    return res + dense(t, p["proj_out"]).reshape(b, h, w, c)


def init(key, cfg: UNetConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    ch = cfg.ch
    temb_dim = 4 * ch
    p: Params = {
        "t1": dense_params(next(ks), ch, temb_dim, dtype=dtype),
        "t2": dense_params(next(ks), temb_dim, temb_dim, dtype=dtype),
        "conv_in": conv_params(next(ks), 3, cfg.latent_ch, ch, dtype=dtype),
        "down": [],
        "mid": {},
        "up": [],
        "norm_out": norm_params(ch, dtype=dtype),
        "conv_out": conv_params(next(ks), 3, ch, cfg.latent_ch, dtype=dtype),
    }
    chans = [ch]  # skip-connection channel bookkeeping
    c_cur = ch
    down = []
    for li, mult in enumerate(cfg.ch_mult):
        c_out = ch * mult
        level = {"res": [], "attn": []}
        has_attn = 2**li in cfg.attn_down
        for _ in range(cfg.n_res_blocks):
            level["res"].append(_res_init(next(ks), c_cur, c_out, temb_dim, dtype))
            level["attn"].append(
                _attn_init(next(ks), c_out, cfg.ctx_dim, dtype) if has_attn else {}
            )
            c_cur = c_out
            chans.append(c_cur)
        if li + 1 < len(cfg.ch_mult):
            level["downsample"] = conv_params(next(ks), 3, c_cur, c_cur, dtype=dtype)
            chans.append(c_cur)
        down.append(level)
    p["down"] = down
    p["mid"] = {
        "res1": _res_init(next(ks), c_cur, c_cur, temb_dim, dtype),
        "attn": _attn_init(next(ks), c_cur, cfg.ctx_dim, dtype),
        "res2": _res_init(next(ks), c_cur, c_cur, temb_dim, dtype),
    }
    up = []
    for li, mult in reversed(list(enumerate(cfg.ch_mult))):
        c_out = ch * mult
        level = {"res": [], "attn": []}
        has_attn = 2**li in cfg.attn_down
        for _ in range(cfg.n_res_blocks + 1):
            c_skip = chans.pop()
            level["res"].append(_res_init(next(ks), c_cur + c_skip, c_out, temb_dim, dtype))
            level["attn"].append(
                _attn_init(next(ks), c_out, cfg.ctx_dim, dtype) if has_attn else {}
            )
            c_cur = c_out
        if li > 0:
            level["upsample"] = conv_params(next(ks), 3, c_cur, c_cur, dtype=dtype)
        up.append(level)
    p["up"] = up
    return p


def apply(params: Params, cfg: UNetConfig, x, t, ctx) -> jax.Array:
    """x [B, h, w, latent_ch] (latent), t [B], ctx [B, 77, ctx_dim] -> eps."""
    t_emb = timestep_embedding(t, cfg.ch).astype(x.dtype)
    temb = dense(silu(dense(t_emb, params["t1"])), params["t2"])
    h = conv2d(x, params["conv_in"], padding=1)
    skips = [h]
    for li, level in enumerate(params["down"]):
        for p_res, p_attn in zip(level["res"], level["attn"]):
            h = _res_apply(p_res, h, temb, cfg.groups)
            if p_attn:
                h = _attn_apply(p_attn, h, ctx, cfg.n_heads, cfg.groups)
            skips.append(h)
        if "downsample" in level:
            h = conv2d(h, level["downsample"], stride=2, padding=1)
            skips.append(h)
    m = params["mid"]
    h = _res_apply(m["res1"], h, temb, cfg.groups)
    h = _attn_apply(m["attn"], h, ctx, cfg.n_heads, cfg.groups)
    h = _res_apply(m["res2"], h, temb, cfg.groups)
    for li, level in enumerate(params["up"]):
        for p_res, p_attn in zip(level["res"], level["attn"]):
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = _res_apply(p_res, h, temb, cfg.groups)
            if p_attn:
                h = _attn_apply(p_attn, h, ctx, cfg.n_heads, cfg.groups)
        if "upsample" in level:
            b, hh, ww, c = h.shape
            h = jax.image.resize(h, (b, hh * 2, ww * 2, c), "nearest")
            h = conv2d(h, level["upsample"], padding=1)
    h = silu(groupnorm(h, params["norm_out"], cfg.groups))
    return conv2d(h, params["conv_out"], padding=1)
