"""Parameter-tree conventions and initializers shared by all model definitions.

Models are pure functions over nested-dict parameter pytrees:

    cfg    = SomeConfig(...)                  # dataclass in repro.configs
    params = init(jax.random.PRNGKey(0), cfg) # pytree of jnp arrays
    y      = apply(params, x, cfg)            # pure function

No Module system: ``jax.jit`` and ``shard_map`` take the pure functions as
they are, and ``jax.eval_shape`` traces a full published config without
allocating it.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp

Params = Any  # nested dict pytree of arrays

DEFAULT_DTYPE = jnp.float32


def keygen(key: jax.Array) -> Iterator[jax.Array]:
    """Infinite stream of fresh PRNG keys."""
    while True:
        key, sub = jax.random.split(key)
        yield sub


def trunc_normal(key, shape, std=0.02, dtype=DEFAULT_DTYPE):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32).astype(dtype)


def lecun_normal(key, shape, fan_in, dtype=DEFAULT_DTYPE):
    std = math.sqrt(1.0 / max(1, fan_in))
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def he_normal(key, shape, fan_in, dtype=DEFAULT_DTYPE):
    std = math.sqrt(2.0 / max(1, fan_in))
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def dense_params(key, d_in, d_out, bias=True, std=None, dtype=DEFAULT_DTYPE) -> Params:
    kw, kb = jax.random.split(key)
    w = (
        trunc_normal(kw, (d_in, d_out), std, dtype)
        if std is not None
        else lecun_normal(kw, (d_in, d_out), d_in, dtype)
    )
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def conv_params(key, k, c_in, c_out, bias=True, groups=1, dtype=DEFAULT_DTYPE) -> Params:
    """HWIO conv kernel; ``groups == c_in`` (with c_in==c_out) => depthwise."""
    kh, kw_ = (k, k) if isinstance(k, int) else k
    fan_in = kh * kw_ * (c_in // groups)
    p = {"w": he_normal(key, (kh, kw_, c_in // groups, c_out), fan_in, dtype)}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype)
    return p


def norm_params(dim, bias=True, dtype=DEFAULT_DTYPE) -> Params:
    p = {"scale": jnp.ones((dim,), dtype)}
    if bias:
        p["b"] = jnp.zeros((dim,), dtype)
    return p


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def stack_layers(init_one: Callable[[jax.Array], Params], key, n: int) -> Params:
    """Initialise ``n`` identical layers as one stacked pytree (leading axis n).

    Stacked layouts let transformer stacks run under ``jax.lax.scan``, which
    keeps the HLO (and XLA compile time) independent of depth.
    """
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)
