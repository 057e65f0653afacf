"""Core neural-net layers in pure JAX (NHWC for convs, [B, T, D] for sequences)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .common import Params


def _kept_narrower(w: jax.Array, x: jax.Array) -> bool:
    """Whether the kernel ``w`` is kept in a narrower type than the activation
    ``x`` (bf16 kernels, f32 activations): ``conv2d`` and ``dense`` then round
    ``x`` to ``w``'s type and accumulate in ``x``'s, so the product takes the
    kernel as it is kept and converts no weight on the call."""
    return jnp.dtype(w.dtype).itemsize < jnp.dtype(x.dtype).itemsize

# ---------------------------------------------------------------------------
# conv / pool
# ---------------------------------------------------------------------------


def conv2d(
    x: jax.Array,
    p: Params,
    stride: int | tuple[int, int] = 1,
    padding="SAME",
    groups: int = 1,
) -> jax.Array:
    """NHWC x HWIO -> NHWC convolution (a kernel kept narrower than ``x``:
    ``_kept_narrower``)."""
    s = (stride, stride) if isinstance(stride, int) else stride
    if isinstance(padding, int):
        padding = [(padding, padding), (padding, padding)]
    w = p["w"]
    narrow = _kept_narrower(w, x)
    y = lax.conv_general_dilated(
        x.astype(w.dtype) if narrow else x,
        w,
        window_strides=s,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=x.dtype if narrow or x.dtype == jnp.float32 else None,
    )
    if "b" in p:
        y = y + p["b"]
    return y


def max_pool(x, k=2, s=2, padding="VALID"):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, k, k, 1), (1, s, s, 1), padding
    )


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))


# ---------------------------------------------------------------------------
# dense / norms / activations
# ---------------------------------------------------------------------------


def dense(x, p: Params):
    """``x @ w + b`` (a kernel kept narrower than ``x``: ``_kept_narrower``)."""
    w = p["w"]
    if _kept_narrower(w, x):
        y = jnp.matmul(x.astype(w.dtype), w, preferred_element_type=x.dtype)
    else:
        y = x @ w
    if "b" in p:
        y = y + p["b"]
    return y


def layernorm(x, p: Params, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps) * p["scale"]
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm(x, p: Params, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (x * lax.rsqrt(var + eps).astype(x.dtype)) * p["scale"]
    return y


def groupnorm(x, p: Params, groups=32, eps=1e-5):
    """NHWC group norm (U-Net)."""
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * lax.rsqrt(var + eps)
    y = xg.reshape(b, h, w, c) * p["scale"]
    if "b" in p:
        y = y + p["b"]
    return y


def batchnorm_inference(x, p: Params, eps=1e-5):
    """Inference-mode BN with folded running stats."""
    return (x - p["mean"]) * lax.rsqrt(p["var"] + eps) * p["scale"] + p["b"]


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def silu(x):
    return jax.nn.silu(x)


def relu(x):
    return jax.nn.relu(x)
