"""Operations and bytes of ViT-L/16's matrix products, from the model sizes
of a configuration file.

``layers(m, batch, act_bytes, w_bytes)`` lists ``(name, flops, bytes)`` for
each matrix product of one call of ``batch`` images: the patch projection,
per encoder block the query/key/value projection, the attention scores, the
weighted sum of values, the output projection and the two MLP layers, and
the head on the class token.  A multiply-add counts two operations; the
bytes are each product's operands, bias and result, read or written once at
the item sizes the run passes.  LayerNorm, softmax and GELU are left out.
"""
from __future__ import annotations


def layers(m, batch, act_bytes=4, w_bytes=4):
    d, f, p, c = m["d_model"], m["d_ff"], m["patch"], m["in_channels"]
    n_p = (m["img_res"] // p) ** 2
    n = n_p + 1
    h, dh = m["n_heads"], m["d_model"] // m["n_heads"]

    def dense(name, rows, k, o):
        return (name, 2.0 * batch * rows * k * o,
                float(batch * rows * (k + o) * act_bytes + (k * o + o) * w_bytes))

    out = [dense("patch_embed", n_p, p * p * c, d)]
    for i in range(m["n_layers"]):
        s = batch * h * n * n
        out += [
            dense(f"block{i}.qkv", n, d, 3 * d),
            (f"block{i}.scores", 2.0 * s * dh,
             float((2 * batch * n * d + s) * act_bytes)),
            (f"block{i}.values", 2.0 * s * dh,
             float((s + 2 * batch * n * d) * act_bytes)),
            dense(f"block{i}.out", n, d, d),
            dense(f"block{i}.fc1", n, d, f),
            dense(f"block{i}.fc2", n, f, d),
        ]
    out.append(dense("head", 1, d, m["num_classes"]))
    return out


def flops_per_image(m):
    return sum(fl for _, fl, _ in layers(m, 1))


def params(m):
    """Parameters of the model: the matrix layers' weights and biases (their
    bytes at batch 0 and one byte an item), the class token, the position
    table and the LayerNorms."""
    d, n = m["d_model"], (m["img_res"] // m["patch"]) ** 2 + 1
    return sum(by for _, _, by in layers(m, 0, 0, 1)) + d + n * d + (4 * m["n_layers"] + 2) * d
