"""Decoder-only LM covering the assigned LM family:

* qwen3-4b        -- dense, GQA (32q/8kv, head 128), qk-norm
* codeqwen1.5-7b  -- dense, MHA (32/32)
* moonshot-v1-16b-a3b -- MoE 64e top-6, GQA 16/16
* deepseek-v3-671b    -- MLA + MoE (1 shared + 256 routed top-8) + MTP

Layer stacks are *stacked pytrees* scanned with ``jax.lax.scan`` so HLO size
and XLA compile time are depth-independent (the 61-layer DeepSeek config
traces as one scanned block).  ``first_k_dense`` leading layers (DeepSeek)
form a second, smaller stack.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .attention import (
    GQAConfig,
    MLAConfig,
    causal_mask,
    decode_mask,
    gqa_apply,
    gqa_init,
    mla_apply,
    mla_init,
)
from .common import Params, dense_params, keygen, norm_params, stack_layers, trunc_normal
from .layers import dense, rmsnorm, silu
from .moe import MoEConfig, moe_apply, moe_init

__all__ = ["LMConfig", "init", "forward", "decode_step", "init_cache"]


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int  # dense-FFN hidden size (used by dense layers)
    vocab: int
    qk_norm: bool = False
    rope_theta: float = 1e6
    attn: str = "gqa"  # "gqa" | "mla"
    mla: MLAConfig | None = None
    moe: MoEConfig | None = None
    first_k_dense: int = 0
    mtp_depth: int = 0  # DeepSeek MTP modules: in the params, read by no forward
    remat: bool = True

    @property
    def gqa(self) -> GQAConfig:
        return GQAConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            d_head=self.d_head,
            qk_norm=self.qk_norm,
            rope_theta=self.rope_theta,
        )

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense if self.moe else 0

    @property
    def n_dense_layers(self) -> int:
        return self.first_k_dense if self.moe else self.n_layers


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _ffn_init(key, d, f, dtype):
    ks = keygen(key)
    return {
        "w1": dense_params(next(ks), d, f, bias=False, dtype=dtype),
        "w3": dense_params(next(ks), d, f, bias=False, dtype=dtype),
        "w2": dense_params(next(ks), f, d, bias=False, dtype=dtype),
    }


def _block_init(key, cfg: LMConfig, moe_layer: bool, dtype) -> Params:
    ka, kf = jax.random.split(key)
    attn = (
        mla_init(ka, cfg.mla, dtype) if cfg.attn == "mla" else gqa_init(ka, cfg.gqa, dtype)
    )
    ffn = (
        moe_init(kf, cfg.moe, dtype)
        if moe_layer
        else _ffn_init(kf, cfg.d_model, cfg.d_ff, dtype)
    )
    return {
        "ln1": norm_params(cfg.d_model, bias=False, dtype=dtype),
        "attn": attn,
        "ln2": norm_params(cfg.d_model, bias=False, dtype=dtype),
        "ffn": ffn,
    }


def init(key, cfg: LMConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    p: Params = {
        "embed": trunc_normal(next(ks), (cfg.vocab, cfg.d_model), 0.02, dtype),
        "final_norm": norm_params(cfg.d_model, bias=False, dtype=dtype),
        "lm_head": dense_params(next(ks), cfg.d_model, cfg.vocab, bias=False, std=0.02, dtype=dtype),
    }
    if cfg.n_dense_layers:
        p["dense_layers"] = stack_layers(
            lambda k: _block_init(k, cfg, moe_layer=False, dtype=dtype),
            next(ks),
            cfg.n_dense_layers,
        )
    if cfg.n_moe_layers:
        p["moe_layers"] = stack_layers(
            lambda k: _block_init(k, cfg, moe_layer=True, dtype=dtype),
            next(ks),
            cfg.n_moe_layers,
        )
    if cfg.mtp_depth:
        p["mtp"] = stack_layers(
            lambda k: {
                "proj": dense_params(k, 2 * cfg.d_model, cfg.d_model, bias=False, dtype=dtype),
                "block": _block_init(k, cfg, moe_layer=bool(cfg.moe), dtype=dtype),
                "norm_h": norm_params(cfg.d_model, bias=False, dtype=dtype),
                "norm_e": norm_params(cfg.d_model, bias=False, dtype=dtype),
            },
            next(ks),
            cfg.mtp_depth,
        )
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _block_apply(
    p: Params,
    cfg: LMConfig,
    moe_layer: bool,
    x,
    positions,
    mask,
    cache=None,
    cache_index=None,
):
    h = rmsnorm(x, p["ln1"])
    if cfg.attn == "mla":
        a, new_cache = mla_apply(p["attn"], cfg.mla, h, positions, mask, cache, cache_index)
    else:
        a, new_cache = gqa_apply(p["attn"], cfg.gqa, h, positions, mask, cache, cache_index)
    x = x + a
    h = rmsnorm(x, p["ln2"])
    if moe_layer:
        b, t, d = h.shape
        y, aux = moe_apply(p["ffn"], cfg.moe, h.reshape(b * t, d))
        y = y.reshape(b, t, d)
        lb = aux["load_balance_loss"]
    else:
        y = dense(silu(dense(h, p["ffn"]["w1"])) * dense(h, p["ffn"]["w3"]), p["ffn"]["w2"])
        lb = jnp.zeros((), jnp.float32)
    return x + y, new_cache, lb


def _scan_stack(p_stack, cfg, moe_layer, x, positions, mask):
    blk = partial(_block_apply, cfg=cfg, moe_layer=moe_layer)

    def body(carry, p_l):
        x, lb = carry
        x, _, lb_l = blk(p_l, x=x, positions=positions, mask=mask)
        return (x, lb + lb_l), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, lb), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), p_stack)
    return x, lb


def trunk(params: Params, cfg: LMConfig, tokens: jax.Array):
    """tokens [B, T] -> (pre-head hidden [B, T, D], load_balance_loss)."""
    b, t = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    mask = causal_mask(t)
    lb = jnp.zeros((), jnp.float32)
    if cfg.n_dense_layers:
        x, lb1 = _scan_stack(params["dense_layers"], cfg, False, x, positions, mask)
        lb = lb + lb1
    if cfg.n_moe_layers:
        x, lb2 = _scan_stack(params["moe_layers"], cfg, True, x, positions, mask)
        lb = lb + lb2
    return x, lb


def forward(params: Params, cfg: LMConfig, tokens: jax.Array) -> tuple[jax.Array, jax.Array]:
    """tokens [B, T] -> (logits [B, T, V], load_balance_loss)."""
    x, lb = trunk(params, cfg, tokens)
    x = rmsnorm(x, params["final_norm"])
    logits = dense(x, params["lm_head"])
    return logits, lb


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------


def init_cache(cfg: LMConfig, batch: int, max_seq: int, dtype=jnp.float32) -> Params:
    """Stacked per-layer KV caches.  GQA: k/v [L, B, S, Hkv, dh]; MLA: the
    compressed latent [L, B, S, kv_lora + rope] (MLA's memory advantage)."""

    def stack(n):
        if cfg.attn == "mla":
            return jnp.zeros(
                (n, batch, max_seq, cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim), dtype
            )
        return {
            "k": jnp.zeros((n, batch, max_seq, cfg.n_kv_heads, cfg.d_head), dtype),
            "v": jnp.zeros((n, batch, max_seq, cfg.n_kv_heads, cfg.d_head), dtype),
        }

    cache: Params = {}
    if cfg.n_dense_layers:
        cache["dense"] = stack(cfg.n_dense_layers)
    if cfg.n_moe_layers:
        cache["moe"] = stack(cfg.n_moe_layers)
    return cache


def _decode_stack(p_stack, cache_stack, cfg, moe_layer, x, positions, mask, index):
    blk = partial(_block_apply, cfg=cfg, moe_layer=moe_layer)

    def body(x, scanned):
        p_l, c_l = scanned
        kv = (c_l["k"], c_l["v"]) if cfg.attn == "gqa" else c_l
        x, new_kv, _ = blk(p_l, x=x, positions=positions, mask=mask, cache=kv, cache_index=index)
        new_c = {"k": new_kv[0], "v": new_kv[1]} if cfg.attn == "gqa" else new_kv
        return x, new_c

    x, new_cache = lax.scan(body, x, (p_stack, cache_stack))
    return x, new_cache


def decode_step(params: Params, cfg: LMConfig, cache: Params, tokens: jax.Array, index):
    """One decode step: tokens [B, 1] at position ``index`` against a cache of
    length max_seq.  Returns (logits [B, vocab], new_cache)."""
    b = tokens.shape[0]
    x = params["embed"][tokens]
    positions = jnp.full((b, 1), index, jnp.int32)
    new_cache: Params = {}
    if cfg.n_dense_layers:
        s_max = (
            cache["dense"].shape[2]
            if cfg.attn == "mla"
            else cache["dense"]["k"].shape[2]
        )
        mask = decode_mask(s_max, index)
        x, new_cache["dense"] = _decode_stack(
            params["dense_layers"], cache["dense"], cfg, False, x, positions, mask, index
        )
    if cfg.n_moe_layers:
        s_max = (
            cache["moe"].shape[2] if cfg.attn == "mla" else cache["moe"]["k"].shape[2]
        )
        mask = decode_mask(s_max, index)
        x, new_cache["moe"] = _decode_stack(
            params["moe_layers"], cache["moe"], cfg, True, x, positions, mask, index
        )
    x = rmsnorm(x, params["final_norm"])
    logits = dense(x, params["lm_head"])[:, 0]
    return logits, new_cache
