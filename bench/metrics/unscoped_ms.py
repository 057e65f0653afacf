"""Device time per model program run, in ms, of the ops whose ``op_name``
names no program scope: XLA's own work, such as ViT-L/16's weight converts
hoisted out of its blocks' loop, and the loop's slicing of the stacked
weights.  Over the runs wholly inside the traced window.  Layer: kernels
(the XLA ops of the one-chip path), by scope."""


def read(r):
    return r.scope_ms(r"\(unscoped\)")
