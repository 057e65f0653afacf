"""Operations and bytes of Swin-B's matrix products, from the model sizes of
a configuration file.

``layers(m, batch, act_bytes, w_bytes)`` lists ``(name, flops, bytes)`` for
each matrix product of one call of ``batch`` images: the patch projection,
per block the query/key/value projection, the attention scores, the
weighted sum of values, the output projection and the two MLP layers, the
patch merging projections between stages, and the head on the pooled
tokens.  A multiply-add counts two operations; the bytes are each product's
operands, bias and result, read or written once at the item sizes the run
passes.  LayerNorm, softmax, GELU, the bias gather, the mask and the window
layout are left out.

``window_attn(m, batch, act_bytes, w_bytes)`` is ``(flops, bytes)`` of the
program scope ``window_attn`` over every block: the projections', scores'
and values' operations, and the bytes no schedule avoids, which are each
block's input read and output written once, its query/key/value and output
projections with their biases and its bias table read once.  The logits
need not reach memory, so they are not counted.
"""
from __future__ import annotations


def _blocks(m):
    """``(stage, block, tokens, width, heads, window tokens)`` of every block
    of one image."""
    side = m["img_res"] // m["patch"]
    for i, (depth, c, h) in enumerate(zip(m["depths"], m["dims"], m["n_heads"])):
        w = min(m["window"], side)
        for j in range(depth):
            yield i, j, side * side, c, h, w * w
        side //= 2


def _dense(name, batch, rows, k, o, act_bytes, w_bytes, bias=True):
    return (name, 2.0 * batch * rows * k * o,
            float(batch * rows * (k + o) * act_bytes + (k * o + bias * o) * w_bytes))


def _attention(i, j, batch, tokens, c, h, n, act_bytes, w_bytes):
    """The block's query/key/value projection, scores, values and output
    projection."""
    logits = batch * h * tokens * n  # n x n per head in each of tokens / n windows
    name = f"stage{i}.block{j}"
    return [
        _dense(f"{name}.qkv", batch, tokens, c, 3 * c, act_bytes, w_bytes),
        (f"{name}.scores", 2.0 * batch * tokens * n * c,
         float(2 * batch * tokens * c * act_bytes + logits * act_bytes)),
        (f"{name}.values", 2.0 * batch * tokens * n * c,
         float(logits * act_bytes + 2 * batch * tokens * c * act_bytes)),
        _dense(f"{name}.out", batch, tokens, c, c, act_bytes, w_bytes),
    ]


def layers(m, batch, act_bytes=4, w_bytes=4):
    p, dims, r = m["patch"], m["dims"], m["mlp_ratio"]
    side = m["img_res"] // p
    out = [_dense("patch_embed", batch, side * side, p * p * m["in_channels"], dims[0],
                  act_bytes, w_bytes)]
    for i, j, tokens, c, h, n in _blocks(m):
        out += _attention(i, j, batch, tokens, c, h, n, act_bytes, w_bytes)
        out += [_dense(f"stage{i}.block{j}.fc1", batch, tokens, c, r * c, act_bytes, w_bytes),
                _dense(f"stage{i}.block{j}.fc2", batch, tokens, r * c, c, act_bytes, w_bytes)]
        if j == m["depths"][i] - 1 and i + 1 < len(dims):
            out.append(_dense(f"stage{i}.merge", batch, tokens // 4, 4 * c, dims[i + 1],
                              act_bytes, w_bytes, bias=False))
    out.append(_dense("head", batch, 1, dims[-1], m["num_classes"], act_bytes, w_bytes))
    return out


def window_attn(m, batch, act_bytes=4, w_bytes=4):
    flops = nbytes = 0.0
    w = m["window"]
    for i, j, tokens, c, h, n in _blocks(m):
        flops += sum(f for _, f, _ in _attention(i, j, batch, tokens, c, h, n, act_bytes, w_bytes))
        nbytes += (2 * batch * tokens * c * act_bytes
                   + (4 * c * c + 4 * c + (2 * w - 1) ** 2 * h) * w_bytes)
    return flops, nbytes


def flops_per_image(m):
    return sum(fl for _, fl, _ in layers(m, 1))


def params(m):
    """Parameters of the model: the matrix layers' weights and biases (their
    bytes at batch 0 and one byte an item), each block's two LayerNorms and
    relative-position bias table, the patch, merge and final LayerNorms."""
    w, dims = m["window"], m["dims"]
    blocks = sum(4 * c + (2 * w - 1) ** 2 * h for _, _, _, c, h, _ in _blocks(m))
    norms = 2 * dims[0] + sum(8 * c for c in dims[:-1]) + 2 * dims[-1]
    return int(sum(by for _, _, by in layers(m, 0, 0, 1)) + blocks + norms)
