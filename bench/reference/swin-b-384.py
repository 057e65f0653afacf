"""Plain Swin-B (Liu et al., arXiv:2103.14030, Table 1), as the authors'
``swin_base_patch4_window12_384`` configuration runs it.

Written apart from the program: patches cut by reshape and projected by one
matmul with a bias, then a LayerNorm; four stages of pre-LN blocks, each
window multi-head self-attention over ``window x window`` tokens with a
learned relative-position bias (indexed as in the authors' code), every
second block on the image rolled by half a window with -100 added to the
logits of key tokens from another of the nine regions the roll brings
together (the authors' three-by-three slices), then an MLP with the exact
(erf) GELU; patch merging between stages concatenates each 2x2
neighbourhood as ``x[0::2, 0::2]``, ``x[1::2, 0::2]``, ``x[0::2, 1::2]``,
``x[1::2, 1::2]``, normalises it and projects it without a bias; a final
LayerNorm, the mean over tokens and a linear head.  LayerNorm eps is 1e-5
(PyTorch's default, which the authors keep).  A stage whose side is no
larger than the window attends over the whole stage, unshifted, as the
authors' code does.  Float32 at the highest matmul precision.

Departures from the published description: no absolute position embedding,
dropout or stochastic depth (the authors' inference path has none of them
either); the query/key/value projection is one ``[C, 3 * C]`` matrix in
(q|k|v, head, dim) order, as the authors pack it; 1000 classes.

``weights`` draws a parameter tree laid out as the program takes it: stages
as a list, each stage's blocks stacked on a leading axis, the patch
projection as an HWIO kernel, the merge projection without a bias.  Draws:
LeCun-normal projections, N(0, 0.02) biases and bias tables, LayerNorm
scales 1 + N(0, 0.02).

``mode`` picks the arithmetic: ``"highest"`` is the reference; ``"bf16"``
and ``"fp8"`` are the lower-precision controls (bf16 storage and operands;
float32 storage with every matmul operand rounded to float8 e4m3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

EPS = 1e-5
MASK = -100.0


def _proj(key, a, b, bias=True):
    k1, k2 = jax.random.split(key)
    p = {"w": a ** -0.5 * jax.random.normal(k1, (a, b))}
    if bias:
        p["b"] = 0.02 * jax.random.normal(k2, (b,))
    return p


def _norm(key, d):
    k1, k2 = jax.random.split(key)
    return {"scale": 1.0 + 0.02 * jax.random.normal(k1, (d,)),
            "b": 0.02 * jax.random.normal(k2, (d,))}


def weights(key, m):
    """Random weights for model sizes ``m``."""
    dims, p, c, w = m["dims"], m["patch"], m["in_channels"], m["window"]
    r = m["mlp_ratio"]
    ks = jax.random.split(key, 4 + 2 * len(dims))

    def block(k, d, heads):
        kb = jax.random.split(k, 7)
        return {"ln1": _norm(kb[0], d), "wqkv": _proj(kb[1], d, 3 * d),
                "wo": _proj(kb[2], d, d),
                "rel_bias": 0.02 * jax.random.normal(kb[3], ((2 * w - 1) ** 2, heads)),
                "ln2": _norm(kb[4], d), "fc1": _proj(kb[5], d, r * d),
                "fc2": _proj(kb[6], r * d, d)}

    stages = []
    for i, (depth, d, heads) in enumerate(zip(m["depths"], dims, m["n_heads"])):
        keys = jax.random.split(ks[4 + 2 * i], depth)
        stage = {"blocks": jax.vmap(lambda k, d=d, heads=heads: block(k, d, heads))(keys)}
        if i + 1 < len(dims):
            km = jax.random.split(ks[5 + 2 * i])
            stage["merge_norm"] = _norm(km[0], 4 * d)
            stage["merge"] = _proj(km[1], 4 * d, dims[i + 1], bias=False)
        stages.append(stage)
    embed = _proj(ks[0], p * p * c, dims[0])
    return {
        "patch_embed": {"w": embed["w"].reshape(p, p, c, dims[0]), "b": embed["b"]},
        "patch_norm": _norm(ks[1], dims[0]),
        "stages": stages,
        "ln": _norm(ks[2], dims[-1]),
        "head": _proj(ks[3], dims[-1], m["num_classes"]),
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


def _linear(mode, x, p):
    act = _operands(mode)[2]
    y = _mm(mode, "...i,io->...o", x, p["w"])
    return y + p["b"].astype(act) if "b" in p else y


def _ln(x, p, act):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + EPS) * p["scale"].astype(act)
            + p["b"].astype(act))


def _gelu(x):
    return 0.5 * x * (1 + lax.erf(x * 0.7071067811865476))


def _partition(x, w):
    """``[B, H, W, C]`` to windows ``[B * nW, w * w, C]``, row-major over
    windows, then over tokens inside a window."""
    b, h, wd, c = x.shape
    x = x.reshape(b, h // w, w, wd // w, w, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, w * w, c)


def _reverse(x, w, b, h, wd):
    c = x.shape[-1]
    x = x.reshape(b, h // w, wd // w, w, w, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, wd, c)


def _relative_index(w):
    """The authors' ``relative_position_index``: ``[w*w, w*w]``."""
    coords = np.stack(np.meshgrid(np.arange(w), np.arange(w), indexing="ij"))
    flat = coords.reshape(2, -1)
    rel = (flat[:, :, None] - flat[:, None, :]).transpose(1, 2, 0).copy()
    rel[:, :, 0] += w - 1
    rel[:, :, 1] += w - 1
    rel[:, :, 0] *= 2 * w - 1
    return rel.sum(-1)


def _attn_mask(h, wd, w, s):
    """The authors' ``attn_mask``: ``[nW, w*w, w*w]``, 0 within a region of
    the rolled image and -100 across regions."""
    img = np.zeros((1, h, wd, 1), np.float32)
    cnt = 0
    for hs in (slice(0, -w), slice(-w, -s), slice(-s, None)):
        for ws in (slice(0, -w), slice(-w, -s), slice(-s, None)):
            img[:, hs, ws, :] = cnt
            cnt += 1
    win = _partition(img, w)[..., 0]
    diff = win[:, None, :] - win[:, :, None]
    return np.where(diff != 0, MASK, 0.0).astype(np.float32)


def block(p, x, heads, w, s, mode="highest"):
    """One Swin block on ``x`` ``[B, H, W, C]``: window size ``w``, shift
    ``s`` (0: none)."""
    act = _operands(mode)[2]
    b, h, wd, c = x.shape
    dh, n = c // heads, w * w
    y = _ln(x, p["ln1"], act)
    if s:
        y = jnp.roll(y, (-s, -s), axis=(1, 2))
    t = _partition(y, w)  # [B * nW, n, C]
    qkv = _linear(mode, t, p["wqkv"]).reshape(-1, n, 3, heads, dh)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    a = _mm(mode, "wqhd,wkhd->whqk", q * jnp.asarray(dh ** -0.5, act), k)
    table = p["rel_bias"].astype(act)[_relative_index(w).reshape(-1)]
    a = a + table.reshape(n, n, heads).transpose(2, 0, 1)[None]
    if s:
        mask = jnp.asarray(_attn_mask(h, wd, w, s), act)
        a = (a.reshape(b, -1, heads, n, n) + mask[None, :, None]).reshape(-1, heads, n, n)
    a = a - a.max(-1, keepdims=True)
    e = jnp.exp(a)
    o = _mm(mode, "whqk,wkhd->wqhd", e / e.sum(-1, keepdims=True), v).reshape(-1, n, c)
    o = _reverse(_linear(mode, o, p["wo"]), w, b, h, wd)
    if s:
        o = jnp.roll(o, (s, s), axis=(1, 2))
    x = x + o
    return x + _linear(mode, _gelu(_linear(mode, _ln(x, p["ln2"], act), p["fc1"])), p["fc2"])


def merge(stage, x, mode="highest"):
    """Patch merging: ``[B, H, W, C]`` to ``[B, H/2, W/2, C']``."""
    act = _operands(mode)[2]
    x = jnp.concatenate([x[:, 0::2, 0::2], x[:, 1::2, 0::2],
                         x[:, 0::2, 1::2], x[:, 1::2, 1::2]], -1)
    return _linear(mode, _ln(x, stage["merge_norm"], act), stage["merge"])


def embed(params, x, m, mode="highest"):
    """Patch tokens ``[B, H/p, W/p, C0]`` of images ``x`` ``[B, H, W, C]``."""
    act = _operands(mode)[2]
    b, p = x.shape[0], m["patch"]
    g = m["img_res"] // p
    patches = (x.reshape(b, g, p, g, p, -1).transpose(0, 1, 3, 2, 4, 5)
               .reshape(b, g, g, -1))
    pe = params["patch_embed"]
    t = _linear(mode, patches, {"w": pe["w"].reshape(-1, pe["w"].shape[-1]), "b": pe["b"]})
    return _ln(t, params["patch_norm"], act)


def head(params, x, mode="highest"):
    """Logits ``[B, classes]`` from the last stage's tokens."""
    act = _operands(mode)[2]
    h = _ln(x, params["ln"], act).mean((1, 2))
    return _linear(mode, h, params["head"]).astype(jnp.float32)


def forward(params, x, m, mode="highest"):
    """Logits ``[B, classes]`` of images ``x`` ``[B, H, W, C]``."""
    t = embed(params, x.astype(_operands(mode)[2]), m, mode)
    for i, stage in enumerate(params["stages"]):
        side = t.shape[1]
        w = min(m["window"], side)
        s = m["window"] // 2 if side > m["window"] else 0
        for j in range(m["depths"][i]):
            p = jax.tree.map(lambda a: a[j], stage["blocks"])
            t = block(p, t, m["n_heads"][i], w, s if j % 2 else 0, mode)
        if "merge" in stage:
            t = merge(stage, t, mode)
    return head(params, t, mode)
