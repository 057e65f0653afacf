"""Swin Transformer (Liu et al., arXiv:2103.14030) -- swin-b.

The block follows the published equations and the authors' code: LayerNorm
with eps 1e-5, the exact (erf) GELU, a learned relative-position bias, a
shifted-window mask that adds -100 to logits across regions, and patch
merging that concatenates the 2x2 neighbours as x0, x1, x2, x3
(``x[0::2, 0::2]``, ``x[1::2, 0::2]``, ``x[0::2, 1::2]``, ``x[1::2, 1::2]``).

Windowed attention has a *bounded receptive field*, so the paper's
receptive-field partitioning applies directly: shifted windows need exactly a
one-window halo, the transformer analogue of HALP's boundary exchange
(see DESIGN.md §4).

Named scopes, for the device trace: ``patch_embed``, ``stage<i>`` holding
``window_attn`` (LN1, windows, attention, the first residual add), ``shift``
(the cyclic rolls), ``mlp`` and ``merge``, then ``head``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import Params, conv_params, dense_params, keygen, norm_params, stack_layers, trunc_normal
from .layers import conv2d, dense, layernorm

__all__ = ["SwinConfig", "init", "apply"]

# nn.LayerNorm's default in the authors' code
LN_EPS = 1e-5
# added to the logits of key tokens from another region of a shifted window
MASKED = -100.0


@dataclass(frozen=True)
class SwinConfig:
    name: str = "swin-b"
    img_res: int = 224
    patch: int = 4
    window: int = 7
    depths: tuple[int, ...] = (2, 2, 18, 2)
    dims: tuple[int, ...] = (128, 256, 512, 1024)
    n_heads: tuple[int, ...] = (4, 8, 16, 32)
    mlp_ratio: int = 4
    num_classes: int = 1000
    in_channels: int = 3
    remat: bool = True


def _block_init(key, dim, heads, window, mlp_ratio, dtype):
    ks = keygen(key)
    return {
        "ln1": norm_params(dim, dtype=dtype),
        "wqkv": dense_params(next(ks), dim, 3 * dim, dtype=dtype),
        "wo": dense_params(next(ks), dim, dim, dtype=dtype),
        "rel_bias": trunc_normal(next(ks), ((2 * window - 1) ** 2, heads), dtype=dtype),
        "ln2": norm_params(dim, dtype=dtype),
        "fc1": dense_params(next(ks), dim, mlp_ratio * dim, dtype=dtype),
        "fc2": dense_params(next(ks), mlp_ratio * dim, dim, dtype=dtype),
    }


def _ln(x, p):
    return layernorm(x, p, eps=LN_EPS)


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


@functools.cache
def _rel_index(window: int) -> np.ndarray:
    """Row of the relative-position bias table for each (query, key) pair of
    a window, ``[n * n]``."""
    coords = np.stack(np.meshgrid(np.arange(window), np.arange(window), indexing="ij")).reshape(2, -1)
    rel = coords[:, :, None] - coords[:, None, :] + (window - 1)  # [2, n, n]
    return (rel[0] * (2 * window - 1) + rel[1]).reshape(-1)


def _window_attention(p, x, heads, window, mask=None):
    """x: [B, nW, n, C] windows -> same shape; ``mask`` [nW, n, n] is added
    to the logits."""
    b, nw, n, c = x.shape
    qkv = dense(x, p["wqkv"]).reshape(b, nw, n, 3, heads, c // heads)
    q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
    logits = jnp.einsum("bwnhd,bwmhd->bwhnm", q, k) / jnp.sqrt(c / heads)
    bias = p["rel_bias"][_rel_index(window)].reshape(n, n, heads)
    logits = logits + bias.transpose(2, 0, 1)[None, None]
    if mask is not None:
        logits = logits + mask[None, :, None]
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(x.dtype)
    out = jnp.einsum("bwhnm,bwmhd->bwnhd", probs, v).reshape(b, nw, n, c)
    return dense(out, p["wo"])


def _to_windows(x, window):
    """[B, H, W, C] -> [B, nW, window*window, C]"""
    b, h, w, c = x.shape
    x = x.reshape(b, h // window, window, w // window, window, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // window) * (w // window), window * window, c)


def _from_windows(x, window, h, w):
    b = x.shape[0]
    c = x.shape[-1]
    x = x.reshape(b, h // window, w // window, window, window, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, w, c)


@functools.cache
def _shift_mask(h: int, w: int, window: int, shift: int) -> np.ndarray:
    """Logit mask of shifted windows, ``[nW, n, n]``: 0 between tokens of one
    region of the rolled image, ``MASKED`` across regions."""
    img = np.zeros((h, w), np.int32)
    bounds = (slice(0, -window), slice(-window, -shift), slice(-shift, None))
    for i, hb in enumerate(bounds):
        for j, wb in enumerate(bounds):
            img[hb, wb] = 3 * i + j
    win = _to_windows(img[None, :, :, None], window)[0, :, :, 0]
    return np.where(win[:, :, None] == win[:, None, :], 0.0, MASKED).astype(np.float32)


def _roll(x, shift):
    with jax.named_scope("shift"):
        return jnp.roll(x, (shift, shift), axis=(1, 2))


def _swin_block(p, x, heads, window, shift):
    """x: [B, H, W, C]."""
    b, h, w, c = x.shape
    with jax.named_scope("window_attn"):
        y = _ln(x, p["ln1"])
    if shift:
        y = _roll(y, -shift)
    with jax.named_scope("window_attn"):
        mask = _shift_mask(h, w, window, shift) if shift else None
        y = _window_attention(p, _to_windows(y, window), heads, window, mask)
        y = _from_windows(y, window, h, w)
    if shift:
        y = _roll(y, shift)
    with jax.named_scope("window_attn"):
        x = x + y
    with jax.named_scope("mlp"):
        y = dense(_ln(x, p["ln2"]), p["fc1"])
        return x + dense(_gelu(y), p["fc2"])


def _merge(stage, x):
    """Patch merging: each 2x2 neighbourhood, as x0, x1, x2, x3, to the next
    stage's width."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 4, 2, 5)
    x = x.reshape(b, h // 2, w // 2, 4 * c)
    return dense(_ln(x, stage["merge_norm"]), stage["merge"])


def init(key, cfg: SwinConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    p: Params = {
        "patch_embed": conv_params(next(ks), cfg.patch, cfg.in_channels, cfg.dims[0], dtype=dtype),
        "patch_norm": norm_params(cfg.dims[0], dtype=dtype),
        "stages": [],
        "ln": norm_params(cfg.dims[-1], dtype=dtype),
        "head": dense_params(next(ks), cfg.dims[-1], cfg.num_classes, dtype=dtype),
    }
    stages = []
    for si, (depth, dim, heads) in enumerate(zip(cfg.depths, cfg.dims, cfg.n_heads)):
        stage = {
            "blocks": stack_layers(
                lambda k, dim=dim, heads=heads: _block_init(
                    k, dim, heads, cfg.window, cfg.mlp_ratio, dtype
                ),
                next(ks),
                depth,
            )
        }
        if si + 1 < len(cfg.depths):
            stage["merge_norm"] = norm_params(4 * dim, dtype=dtype)
            stage["merge"] = dense_params(next(ks), 4 * dim, cfg.dims[si + 1], bias=False, dtype=dtype)
        stages.append(stage)
    p["stages"] = stages
    return p


def _stage(stage, cfg: SwinConfig, si: int, x):
    heads = cfg.n_heads[si]
    hcur = x.shape[1]
    # a stage no larger than one window attends over all of it, unshifted
    shift = cfg.window // 2 if hcur > cfg.window else 0
    win = min(cfg.window, hcur)
    blocks = stage["blocks"]
    depth = cfg.depths[si]
    if depth >= 6 and depth % 2 == 0:
        # scan over (regular, shifted) block *pairs* so HLO size stays bounded
        pair = jax.tree_util.tree_map(lambda a: a.reshape(depth // 2, 2, *a.shape[1:]), blocks)

        def pair_body(h, p_pair):
            p0 = jax.tree_util.tree_map(lambda a: a[0], p_pair)
            p1 = jax.tree_util.tree_map(lambda a: a[1], p_pair)
            h = _swin_block(p0, h, heads, win, 0)
            h = _swin_block(p1, h, heads, win, shift)
            return h, None

        if cfg.remat:
            pair_body = jax.checkpoint(pair_body, prevent_cse=False)
        x, _ = lax.scan(pair_body, x, pair)
    else:
        for li in range(depth):
            p_l = jax.tree_util.tree_map(lambda a: a[li], blocks)
            x = _swin_block(p_l, x, heads, win, shift if li % 2 else 0)
    if "merge" in stage:
        with jax.named_scope("merge"):
            x = _merge(stage, x)
    return x


def apply(params: Params, cfg: SwinConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("patch_embed"):
        x = conv2d(x, params["patch_embed"], stride=cfg.patch, padding="VALID")
        x = _ln(x, params["patch_norm"])
    for si, stage in enumerate(params["stages"]):
        with jax.named_scope(f"stage{si}"):
            x = _stage(stage, cfg, si, x)
    with jax.named_scope("head"):
        x = _ln(x, params["ln"])
        return dense(jnp.mean(x, axis=(1, 2)), params["head"])
