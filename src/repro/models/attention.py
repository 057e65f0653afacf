"""Attention variants for the LM family: GQA (+RoPE, qk-norm), MLA (DeepSeek),
with KV caches for decode.  Shapes: x [B, T, D]; caches [B, S, ...]."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from .common import Params, dense_params, keygen, norm_params
from .layers import dense, rmsnorm

# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(d: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, T, H, d]; positions: [B, T] (absolute)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # [d/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, T, d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GQAConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    qk_norm: bool = False
    rope_theta: float = 1e6


def gqa_init(key, cfg: GQAConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    p = {
        "wq": dense_params(next(ks), cfg.d_model, cfg.n_heads * cfg.d_head, bias=False, dtype=dtype),
        "wk": dense_params(next(ks), cfg.d_model, cfg.n_kv_heads * cfg.d_head, bias=False, dtype=dtype),
        "wv": dense_params(next(ks), cfg.d_model, cfg.n_kv_heads * cfg.d_head, bias=False, dtype=dtype),
        "wo": dense_params(next(ks), cfg.n_heads * cfg.d_head, cfg.d_model, bias=False, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_params(cfg.d_head, bias=False, dtype=dtype)
        p["k_norm"] = norm_params(cfg.d_head, bias=False, dtype=dtype)
    return p


CHUNK_MIN_T = 4096  # query lengths >= this use the O(S)-memory chunked path
Q_CHUNK = 1024


def _sdpa(q, k, v, mask, scale):
    """q: [B,T,H,d] k,v: [B,S,Hkv,d] -> [B,T,H,d]; grouped heads broadcast."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    q = q.reshape(b, t, hkv, g, d)
    logits = jnp.einsum("bthgd,bshd->bhgts", q, k) * scale
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v)
    return out.reshape(b, t, h, d)


def _sdpa_chunked_causal(q, k, v, scale, chunk=Q_CHUNK):
    """Causal attention scanned over query blocks: peak memory is one
    [B, H, chunk, S] logits block instead of [B, H, T, S] (the pure-JAX
    flash-equivalent used by long prefill shapes; the Pallas TPU
    kernel in repro.kernels.attention is the on-device analogue)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    nblk = t // chunk
    qb = q.reshape(b, nblk, chunk, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kv_pos = jnp.arange(k.shape[1])

    # NOTE: the block index is the scan CARRY, not a scanned arange -- a
    # scanned-input mask is loop-invariant per block, so XLA hoists and stacks
    # all nblk [chunk, S] masks into one HBM-resident input.  The body is
    # rematerialised so the backward pass recomputes the [chunk, S] probs
    # instead of saving nblk stacked f32 residuals (flash-style; compute is
    # far from the bound here -- §Perf iteration 2).
    def body(blk, qi):  # qi [B,Hkv,G,chunk,d]
        q_pos = blk * chunk + jnp.arange(chunk)
        logits = jnp.einsum("bhgtd,bshd->bhgts", qi, k) * scale
        mask = (q_pos[:, None] >= kv_pos[None, :])[None, None, None]
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(qi.dtype)
        return blk + 1, jnp.einsum("bhgts,bshd->bhgtd", probs, v)

    body = jax.checkpoint(body, prevent_cse=False)
    _, outs = lax.scan(body, jnp.int32(0), qb)
    # outs [nblk, B, Hkv, G, chunk, d] -> [B, T, H, d]
    outs = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, t, h, d)
    return outs


def gqa_apply(
    p: Params,
    cfg: GQAConfig,
    x: jax.Array,
    positions: jax.Array,
    mask: jax.Array,
    kv: tuple[jax.Array, jax.Array] | None = None,
    cache_index: jax.Array | None = None,
):
    """Returns (out, (k_cache, v_cache)).

    Forward (prefill): kv=None, mask [B,1,1,T,T] causal.  Decode: kv = full caches
    [B,S,max] and ``cache_index`` the write position; x is the new token block.
    """
    b, t, _ = x.shape
    q = dense(x, p["wq"]).reshape(b, t, cfg.n_heads, cfg.d_head)
    k = dense(x, p["wk"]).reshape(b, t, cfg.n_kv_heads, cfg.d_head)
    v = dense(x, p["wv"]).reshape(b, t, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if kv is not None:
        k_cache, v_cache = kv
        k_cache = lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, cache_index, 0, 0))
        v_cache = lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, cache_index, 0, 0))
        k, v = k_cache, v_cache
    scale = cfg.d_head ** -0.5
    if kv is None and t >= CHUNK_MIN_T and t % Q_CHUNK == 0:
        out = _sdpa_chunked_causal(q, k, v, scale)
    else:
        out = _sdpa(q, k, v, mask, scale)
    out = dense(out.reshape(b, t, cfg.n_heads * cfg.d_head), p["wo"])
    return out, (k, v)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3, arXiv:2412.19437)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def mla_init(key, cfg: MLAConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    h = cfg.n_heads
    return {
        "wdq": dense_params(next(ks), cfg.d_model, cfg.q_lora_rank, bias=False, dtype=dtype),
        "q_norm": norm_params(cfg.q_lora_rank, bias=False, dtype=dtype),
        "wuq": dense_params(next(ks), cfg.q_lora_rank, h * cfg.qk_head_dim, bias=False, dtype=dtype),
        "wdkv": dense_params(next(ks), cfg.d_model, cfg.kv_lora_rank, bias=False, dtype=dtype),
        "kv_norm": norm_params(cfg.kv_lora_rank, bias=False, dtype=dtype),
        "wukv": dense_params(
            next(ks), cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            bias=False, dtype=dtype,
        ),
        "wkr": dense_params(next(ks), cfg.d_model, cfg.qk_rope_head_dim, bias=False, dtype=dtype),
        "wo": dense_params(next(ks), h * cfg.v_head_dim, cfg.d_model, bias=False, dtype=dtype),
    }


def mla_apply(
    p: Params,
    cfg: MLAConfig,
    x: jax.Array,
    positions: jax.Array,
    mask: jax.Array,
    cache: jax.Array | None = None,  # [B, S, kv_lora + rope] compressed KV cache
    cache_index: jax.Array | None = None,
):
    """Multi-head Latent Attention.  The cache stores only the *compressed*
    latent (kv_lora_rank + rope dims per token) -- MLA's memory win."""
    b, t, _ = x.shape
    h = cfg.n_heads
    cq = rmsnorm(dense(x, p["wdq"]), p["q_norm"])
    q = dense(cq, p["wuq"]).reshape(b, t, h, cfg.qk_head_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = rmsnorm(dense(x, p["wdkv"]), p["kv_norm"])  # [B,T,kv_lora]
    k_rope_new = apply_rope(
        dense(x, p["wkr"])[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0]  # [B,T,rope] shared across heads
    latent_new = jnp.concatenate([ckv, k_rope_new], axis=-1)
    if cache is not None:
        cache = lax.dynamic_update_slice(
            cache, latent_new.astype(cache.dtype), (0, cache_index, 0)
        )
        latent = cache
    else:
        latent = latent_new
    ckv_all = latent[..., : cfg.kv_lora_rank]
    k_rope = latent[..., cfg.kv_lora_rank :]

    kv = dense(ckv_all, p["wukv"]).reshape(
        b, latent.shape[1], h, cfg.qk_nope_head_dim + cfg.v_head_dim
    )
    k_nope, v = kv[..., : cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim :]

    scale = cfg.qk_head_dim ** -0.5
    if cache is None and t >= 4096 and t % 1024 == 0:
        out = _mla_chunked_causal(q_nope, q_rope, k_nope, k_rope, v, scale)
    else:
        logits = (
            jnp.einsum("bthd,bshd->bhts", q_nope, k_nope)
            + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope)
        ) * scale
        logits = jnp.where(
            mask[:, :, 0] if mask.ndim == 5 else mask, logits, jnp.finfo(logits.dtype).min
        )
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(x.dtype)
        out = jnp.einsum("bhts,bshd->bthd", probs, v)
    out = dense(out.reshape(b, t, h * cfg.v_head_dim), p["wo"])
    return out, cache


def _mla_chunked_causal(q_nope, q_rope, k_nope, k_rope, v, scale, chunk=1024):
    b, t, h, dn = q_nope.shape
    nblk = t // chunk
    qn = q_nope.reshape(b, nblk, chunk, h, dn).transpose(1, 0, 3, 2, 4)
    qr = q_rope.reshape(b, nblk, chunk, h, q_rope.shape[-1]).transpose(1, 0, 3, 2, 4)
    kv_pos = jnp.arange(k_nope.shape[1])

    def body(blk, inp):  # blk carried: see _sdpa_chunked_causal note
        qni, qri = inp
        q_pos = blk * chunk + jnp.arange(chunk)
        logits = (
            jnp.einsum("bhtd,bshd->bhts", qni, k_nope)
            + jnp.einsum("bhtd,bsd->bhts", qri, k_rope)
        ) * scale
        mask = (q_pos[:, None] >= kv_pos[None, :])[None, None]
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(qni.dtype)
        return blk + 1, jnp.einsum("bhts,bshd->bhtd", probs, v)

    body = jax.checkpoint(body, prevent_cse=False)
    _, outs = lax.scan(body, jnp.int32(0), (qn, qr))
    return outs.transpose(1, 0, 3, 2, 4).reshape(b, t, h, v.shape[-1])


def causal_mask(t: int, dtype=jnp.bool_) -> jax.Array:
    return jnp.tril(jnp.ones((t, t), dtype))[None, None, None]  # [1,1,1,T,T]


def decode_mask(s_max: int, cache_index: jax.Array) -> jax.Array:
    """[1,1,1,1,S]: positions <= cache_index are visible."""
    return (jnp.arange(s_max) <= cache_index)[None, None, None, None]
