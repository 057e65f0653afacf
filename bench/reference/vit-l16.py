"""Plain ViT-L/16 (Dosovitskiy et al., arXiv:2010.11929, Table 1).

Written apart from the program: patches cut by reshape and projected by one
matmul, a learned class token and position table, pre-LN encoder blocks
(multi-head self-attention, then an MLP with the tanh form of GELU, as in the
authors' JAX code), a final LayerNorm and a linear head on the class token;
float32 at the highest matmul precision.  ``weights``
draws a parameter tree laid out as the program takes it: the encoder blocks
stacked on a leading axis, the patch projection as an HWIO kernel, the
query/key/value projections packed as ``[D, 3 * D]`` in (q|k|v, head, dim)
order.

``mode`` picks the arithmetic: ``"highest"`` is the reference; ``"bf16"``
and ``"fp8"`` are the lower-precision controls (bf16 storage and operands;
float32 storage with every matmul operand rounded to float8 e4m3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _dense(key, a, b):
    k1, k2 = jax.random.split(key)
    return {"w": a ** -0.5 * jax.random.normal(k1, (a, b)),
            "b": 0.02 * jax.random.normal(k2, (b,))}


def _norm(key, d):
    k1, k2 = jax.random.split(key)
    return {"scale": 1.0 + 0.02 * jax.random.normal(k1, (d,)),
            "b": 0.02 * jax.random.normal(k2, (d,))}


def weights(key, m):
    """Random weights for model sizes ``m``."""
    d, f, p, c = m["d_model"], m["d_ff"], m["patch"], m["in_channels"]
    n_tok = (m["img_res"] // p) ** 2 + 1
    ks = jax.random.split(key, 8)

    def block(k):
        kb = jax.random.split(k, 6)
        return {"ln1": _norm(kb[0], d), "wqkv": _dense(kb[1], d, 3 * d),
                "wo": _dense(kb[2], d, d), "ln2": _norm(kb[3], d),
                "fc1": _dense(kb[4], d, f), "fc2": _dense(kb[5], f, d)}

    embed = _dense(ks[0], p * p * c, d)
    return {
        "patch_embed": {"w": embed["w"].reshape(p, p, c, d), "b": embed["b"]},
        "cls": 0.02 * jax.random.normal(ks[1], (1, 1, d)),
        "pos": 0.02 * jax.random.normal(ks[2], (1, n_tok, d)),
        "blocks": jax.vmap(block)(jax.random.split(ks[3], m["n_layers"])),
        "ln": _norm(ks[4], d),
        "head": _dense(ks[5], d, m["num_classes"]),
    }


def _operands(mode):
    if mode == "highest":
        return (lambda t: t), lax.Precision.HIGHEST, jnp.float32
    if mode == "bf16":
        return (lambda t: t.astype(jnp.bfloat16)), None, jnp.bfloat16
    if mode == "fp8":
        return ((lambda t: t.astype(jnp.float8_e4m3fn).astype(jnp.float32)),
                lax.Precision.HIGHEST, jnp.float32)
    raise ValueError(f"unknown mode {mode!r}")


def _mm(mode, eq, a, b):
    cast, prec, act = _operands(mode)
    return jnp.einsum(eq, cast(a), cast(b), precision=prec).astype(act)


def _ln(x, p, act):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + 1e-6) * p["scale"].astype(act)
            + p["b"].astype(act))


def _gelu(x):
    return 0.5 * x * (1 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def embed(params, x, m, mode="highest"):
    """Tokens ``[B, 1 + patches, D]`` of images ``x`` ``[B, H, W, C]``."""
    act = _operands(mode)[2]
    b, p, d = x.shape[0], m["patch"], m["d_model"]
    g = m["img_res"] // p
    patches = (x.reshape(b, g, p, g, p, -1).transpose(0, 1, 3, 2, 4, 5)
               .reshape(b, g * g, -1))
    w = params["patch_embed"]["w"].reshape(-1, d)
    t = _mm(mode, "bnk,kd->bnd", patches, w) + params["patch_embed"]["b"].astype(act)
    cls = jnp.broadcast_to(params["cls"].astype(act), (b, 1, d))
    return jnp.concatenate([cls, t], axis=1) + params["pos"].astype(act)


def block(p, x, m, mode="highest"):
    """One pre-LN encoder block on tokens ``x``."""
    act = _operands(mode)[2]
    b, n, d = x.shape
    h_, dh = m["n_heads"], d // m["n_heads"]
    h = _ln(x, p["ln1"], act)
    qkv = (_mm(mode, "bnd,de->bne", h, p["wqkv"]["w"])
           + p["wqkv"]["b"].astype(act)).reshape(b, n, 3, h_, dh)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = _mm(mode, "bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(dh, act))
    s = s - s.max(-1, keepdims=True)
    e = jnp.exp(s)
    a = _mm(mode, "bhqk,bkhd->bqhd", e / e.sum(-1, keepdims=True), v).reshape(b, n, d)
    x = x + _mm(mode, "bnd,de->bne", a, p["wo"]["w"]) + p["wo"]["b"].astype(act)
    h = _ln(x, p["ln2"], act)
    h = _gelu(_mm(mode, "bnd,df->bnf", h, p["fc1"]["w"]) + p["fc1"]["b"].astype(act))
    return x + _mm(mode, "bnf,fd->bnd", h, p["fc2"]["w"]) + p["fc2"]["b"].astype(act)


def head(params, x, mode="highest"):
    """Logits ``[B, classes]`` from the tokens after the last block."""
    act = _operands(mode)[2]
    h = _ln(x[:, 0], params["ln"], act)
    return (_mm(mode, "bd,dc->bc", h, params["head"]["w"])
            + params["head"]["b"].astype(act)).astype(jnp.float32)


def forward(params, x, m, mode="highest"):
    """Logits ``[B, classes]`` of images ``x`` ``[B, H, W, C]``."""
    t = embed(params, x.astype(_operands(mode)[2]), m, mode)
    for i in range(m["n_layers"]):
        t = block(jax.tree.map(lambda a: a[i], params["blocks"]), t, m, mode)
    return head(params, t, mode)
