"""Plain VGG-16 (configuration D, Simonyan & Zisserman, arXiv:1409.1556).

Written apart from the program: whole-image 'SAME' convolutions, 2x2 max
pooling by reshape, and the three dense layers, in float32 at the highest
matmul precision.  ``weights`` draws a parameter tree laid out as the program
takes it (``features``: per conv ``{"w": HWIO, "b"}`` and ``{}`` per pool;
``head``: three ``{"w", "b"}``), so the same draw feeds both.

``mode`` picks the arithmetic: ``"highest"`` is the reference; ``"bf16"``
and ``"fp8"`` are the lower-precision controls (bf16 storage and operands;
float32 storage with every conv and matmul operand rounded to float8 e4m3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def widths(m):
    """``(convs, channels)`` of each block: the published ``blocks`` scaled
    by ``width_mult`` (1 at the published size), at least 8 channels."""
    return [(reps, max(8, int(c * m.get("width_mult", 1.0)))) for reps, c in m["blocks"]]


def _convs(m):
    c_in = m["in_channels"]
    for reps, c_out in widths(m):
        for _ in range(reps):
            yield c_in, c_out
            c_in = c_out
        yield None


def weights(key, m):
    """Random weights for model sizes ``m``: He-normal convs, LeCun-normal
    dense layers, small random biases (so the bias adds are exercised)."""
    feats, head = [], []
    c_last = widths(m)[-1][1]
    rows = m["img_res"] // 2 ** len(m["blocks"])
    dims = [c_last * rows * rows, *m["fc_dims"], m["num_classes"]]
    n = sum(1 for c in _convs(m) if c) + len(dims) - 1
    keys = iter(jax.random.split(key, 2 * n))
    for c in _convs(m):
        if c is None:
            feats.append({})
            continue
        c_in, c_out = c
        std = (2.0 / (9 * c_in)) ** 0.5
        feats.append({"w": std * jax.random.normal(next(keys), (3, 3, c_in, c_out)),
                      "b": 0.01 * jax.random.normal(next(keys), (c_out,))})
    for a, b in zip(dims[:-1], dims[1:]):
        head.append({"w": a ** -0.5 * jax.random.normal(next(keys), (a, b)),
                     "b": 0.01 * jax.random.normal(next(keys), (b,))})
    return {"features": feats, "head": head}


def _operands(mode):
    """``(cast, precision)``: how each conv/matmul sees its operands."""
    if mode == "highest":
        return (lambda t: t), lax.Precision.HIGHEST
    if mode == "bf16":
        return (lambda t: t.astype(jnp.bfloat16)), None
    if mode == "fp8":
        return (lambda t: t.astype(jnp.float8_e4m3fn).astype(jnp.float32)), lax.Precision.HIGHEST
    raise ValueError(f"unknown mode {mode!r}")


def forward(params, x, m, mode="highest"):
    """Logits ``[B, classes]`` of images ``x`` ``[B, H, W, C]``; ``m`` is
    unused (the layer list is in ``params``)."""
    cast, prec = _operands(mode)
    act = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = x.astype(act)
    for p in params["features"]:
        if not p:
            b, h, w, c = x.shape
            x = x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
            continue
        y = lax.conv_general_dilated(cast(x), cast(p["w"]), (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                     precision=prec)
        x = jnp.maximum(y.astype(act) + p["b"].astype(act), 0)
    x = x.reshape(x.shape[0], -1)
    layers = params["head"]
    for i, p in enumerate(layers):
        x = jnp.dot(cast(x), cast(p["w"]), precision=prec).astype(act) + p["b"].astype(act)
        if i < len(layers) - 1:
            x = jnp.maximum(x, 0)
    return x.astype(jnp.float32)
