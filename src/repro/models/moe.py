"""Mixture-of-Experts FFN with capacity-based top-k routing (GShard-style).

The dispatch is expressed as gathers/scatters over an [E, C] slot table so the
expert compute is a single ``einsum('ecd,edf->ecf')`` -- the layout GSPMD
shards cleanly with experts on the ``model`` mesh axis (expert parallelism).
``capacity_factor`` >= E/top_k reproduces dropless routing exactly (used by the
tests' per-token oracle comparison); production configs use ~1.25.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import Params, dense_params, keygen
from .layers import dense, silu

__all__ = ["MoEConfig", "moe_init", "moe_apply", "router_topk"]


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_ff: int  # per-expert hidden dim
    n_shared: int = 0  # always-on shared experts (DeepSeek-V3: 1)
    capacity_factor: float = 1.25
    router_bias: bool = False  # aux-loss-free bias (DeepSeek-V3)
    dropless_below: int = 256  # token counts <= this route drop-free (decode)


def moe_init(key, cfg: MoEConfig, dtype=jnp.float32) -> Params:
    ks = keygen(key)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff

    def expert_stack(k):
        std = (1.0 / d) ** 0.5
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "w1": std * jax.random.normal(k1, (e, d, f), jnp.float32).astype(dtype),
            "w3": std * jax.random.normal(k2, (e, d, f), jnp.float32).astype(dtype),
            "w2": (1.0 / f) ** 0.5
            * jax.random.normal(k3, (e, f, d), jnp.float32).astype(dtype),
        }

    p = {
        "router": dense_params(next(ks), d, e, bias=False, std=0.02, dtype=dtype),
        "experts": expert_stack(next(ks)),
    }
    if cfg.router_bias:
        p["router_b"] = jnp.zeros((e,), dtype)
    if cfg.n_shared:
        fs = cfg.d_ff * cfg.n_shared
        p["shared"] = {
            "w1": dense_params(next(ks), d, fs, bias=False, dtype=dtype),
            "w3": dense_params(next(ks), d, fs, bias=False, dtype=dtype),
            "w2": dense_params(next(ks), fs, d, bias=False, dtype=dtype),
        }
    return p


def router_topk(p: Params, cfg: MoEConfig, x: jax.Array):
    """x: [T, D] -> (gates [T,k] renormalised, ids [T,k], router probs [T,E])."""
    logits = dense(x, p["router"]).astype(jnp.float32)
    if "router_b" in p:
        logits = logits + p["router_b"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates.astype(x.dtype), ids, probs


def _dispatch(ids: jax.Array, e: int, capacity: int):
    """ids: [T, k] expert assignment -> (slot_expert [T,k], slot_pos [T,k], keep).

    Sort-based position-in-expert (O(N log N) bytes): a stable argsort groups
    the flattened token-major slots by expert; each slot's position is its
    rank minus the first rank of its expert.  Identical assignment semantics
    to the GShard one-hot cumsum (stable sort preserves token-major priority)
    at ~E x lower memory traffic -- the cumsum materialises [T*k, E] and
    prefix-scans it in log passes."""
    t, k = ids.shape
    flat = ids.reshape(-1)  # [N = T*k], token-major order
    n = flat.shape[0]
    order = jnp.argsort(flat, stable=True)  # slots grouped by expert
    ranks = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    sorted_ids = flat[order]
    first_rank = jnp.searchsorted(sorted_ids, jnp.arange(e, dtype=flat.dtype))
    pos = ranks - first_rank[flat].astype(jnp.int32)  # position within expert
    keep = pos < capacity
    return flat.reshape(t, k), pos.reshape(t, k), keep.reshape(t, k)


def moe_apply(p: Params, cfg: MoEConfig, x: jax.Array) -> tuple[jax.Array, dict]:
    """x: [T, D] -> (y [T, D], aux dict with load-balancing stats)."""
    tkn, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    capacity = max(1, int(cfg.capacity_factor * tkn * k / e))
    # dropless for small token counts (decode steps): per-expert load can never
    # exceed the token count, so capacity = tkn makes routing exact.
    if tkn <= cfg.dropless_below:
        capacity = max(capacity, tkn)
    gates, ids, probs = router_topk(p, cfg, x)
    flat_e, pos, keep = _dispatch(ids, e, capacity)

    # gather tokens into [E, C, D] slots; dropped (token, choice) pairs go to a
    # dummy slot so kept slots have exactly one writer.
    dummy = e * capacity
    slot = jnp.where(keep, flat_e * capacity + pos, dummy)  # [T, k]
    token_idx = jnp.broadcast_to(jnp.arange(tkn)[:, None], (tkn, k))
    xs = jnp.zeros((e * capacity + 1, d), x.dtype)
    xs = xs.at[slot.reshape(-1)].set(x[token_idx.reshape(-1)])
    xs = xs[:-1].reshape(e, capacity, d)

    w = p["experts"]
    h = jnp.einsum("ecd,edf->ecf", xs, w["w1"])
    g = jnp.einsum("ecd,edf->ecf", xs, w["w3"])
    y_e = jnp.einsum("ecf,efd->ecd", silu(h) * g, w["w2"]).reshape(e * capacity, d)

    # combine back with gates (dropped choices contribute zero)
    y_pad = jnp.concatenate([y_e, jnp.zeros((1, d), y_e.dtype)], axis=0)
    picked = y_pad[slot.reshape(-1)].reshape(tkn, k, d)
    y = jnp.sum(picked * (gates * keep)[..., None], axis=1)

    if cfg.n_shared:
        s = p["shared"]
        y = y + dense(silu(dense(x, s["w1"])) * dense(x, s["w3"]), s["w2"])

    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        (jax.nn.one_hot(ids, e, dtype=jnp.float32) * keep[..., None]).sum(1), axis=0
    ) / k
    aux = {
        "load_balance_loss": e * jnp.sum(me * ce),
        "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y, aux
