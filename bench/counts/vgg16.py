"""Operations and bytes of VGG-16's convolutions and dense layers, from the
model sizes of a configuration file.

``layers(m, batch, act_bytes, w_bytes)`` lists ``(name, flops, bytes)`` for
each layer that runs on the matrix unit, for one call of ``batch`` images:
a multiply-add counts two operations, and the bytes are the layer's input,
weights, bias and output, each read or written once at the item sizes the
run passes (``act_bytes`` for activations, ``w_bytes`` for weights).  Pools
and ReLUs are left out: they do no matrix work and their bytes are counted
as the neighbouring layers' outputs and inputs.
"""
from __future__ import annotations


def layers(m, batch, act_bytes=4, w_bytes=4):
    out = []
    res, c_in = m["img_res"], m["in_channels"]
    widths = [(reps, max(8, int(c * m.get("width_mult", 1.0)))) for reps, c in m["blocks"]]
    for b, (reps, c_out) in enumerate(widths, start=1):
        for r in range(1, reps + 1):
            hw = res * res
            flops = 2.0 * batch * hw * 9 * c_in * c_out
            nbytes = (batch * hw * (c_in + c_out) * act_bytes
                      + (9 * c_in * c_out + c_out) * w_bytes)
            out.append((f"conv{b}_{r}", flops, float(nbytes)))
            c_in = c_out
        res //= 2
    dims = [c_in * res * res, *m["fc_dims"], m["num_classes"]]
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]), start=1):
        out.append((f"fc{i}", 2.0 * batch * a * b,
                    float(batch * (a + b) * act_bytes + (a * b + b) * w_bytes)))
    return out


def flops_per_image(m):
    return sum(f for _, f, _ in layers(m, 1))


def params(m):
    """Parameters of the model: every conv and dense weight and bias (their
    bytes at batch 0 and one byte an item)."""
    return sum(by for _, _, by in layers(m, 0, 0, 1))
