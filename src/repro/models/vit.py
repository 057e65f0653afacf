"""ViT (Dosovitskiy et al., arXiv:2010.11929) -- vit-l16 and friends."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from .common import Params, conv_params, dense_params, keygen, norm_params, stack_layers, trunc_normal
from .layers import conv2d, dense, gelu, layernorm

__all__ = ["ViTConfig", "init", "apply", "vit_block_init", "vit_block_apply"]


@dataclass(frozen=True)
class ViTConfig:
    name: str = "vit-l16"
    img_res: int = 224
    patch: int = 16
    n_layers: int = 24
    d_model: int = 1024
    n_heads: int = 16
    d_ff: int = 4096
    num_classes: int = 1000
    in_channels: int = 3
    remat: bool = True

    @property
    def n_tokens(self) -> int:
        return (self.img_res // self.patch) ** 2 + 1  # + cls token


def vit_block_init(key, d_model, n_heads, d_ff, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    return {
        "ln1": norm_params(d_model, dtype=dtype),
        "wqkv": dense_params(next(ks), d_model, 3 * d_model, dtype=dtype),
        "wo": dense_params(next(ks), d_model, d_model, dtype=dtype),
        "ln2": norm_params(d_model, dtype=dtype),
        "fc1": dense_params(next(ks), d_model, d_ff, dtype=dtype),
        "fc2": dense_params(next(ks), d_ff, d_model, dtype=dtype),
    }


def vit_block_apply(p: Params, x: jax.Array, n_heads: int) -> jax.Array:
    """Pre-LN transformer encoder block; x [B, N, D]."""
    b, n, d = x.shape
    with jax.named_scope("attn"):
        h = layernorm(x, p["ln1"])
        qkv = dense(h, p["wqkv"]).reshape(b, n, 3, n_heads, d // n_heads)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) / jnp.sqrt(d / n_heads)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(x.dtype)
        a = jnp.einsum("bhnm,bmhd->bnhd", probs, v).reshape(b, n, d)
        x = x + dense(a, p["wo"])
    with jax.named_scope("mlp"):
        h = layernorm(x, p["ln2"])
        return x + dense(gelu(dense(h, p["fc1"])), p["fc2"])


def init(key, cfg: ViTConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    return {
        "patch_embed": conv_params(next(ks), cfg.patch, cfg.in_channels, cfg.d_model, dtype=dtype),
        "cls": trunc_normal(next(ks), (1, 1, cfg.d_model), dtype=dtype),
        "pos": trunc_normal(next(ks), (1, cfg.n_tokens, cfg.d_model), dtype=dtype),
        "blocks": stack_layers(
            lambda k: vit_block_init(k, cfg.d_model, cfg.n_heads, cfg.d_ff, dtype),
            next(ks),
            cfg.n_layers,
        ),
        "ln": norm_params(cfg.d_model, dtype=dtype),
        "head": dense_params(next(ks), cfg.d_model, cfg.num_classes, dtype=dtype),
    }


def apply(params: Params, cfg: ViTConfig, x: jax.Array) -> jax.Array:
    """x [B, H, W, C] -> logits [B, classes]."""
    b = x.shape[0]
    with jax.named_scope("patch_embed"):
        x = conv2d(x, params["patch_embed"], stride=cfg.patch, padding="VALID")
        x = x.reshape(b, -1, cfg.d_model)
        x = jnp.concatenate([jnp.broadcast_to(params["cls"], (b, 1, cfg.d_model)), x], axis=1)
        x = x + params["pos"]

    def body(h, p_l):
        return vit_block_apply(p_l, h, cfg.n_heads), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = lax.scan(body, x, params["blocks"])
    with jax.named_scope("head"):
        x = layernorm(x, params["ln"])
        return dense(x[:, 0], params["head"])
