"""Device time per model program run, in ms, of the ops in the program
scopes ``stage<i>/shift`` (Swin's cyclic rolls of the image before and after
a shifted block's window attention), over the runs wholly inside the traced
window.  Layer: kernels (the XLA ops of the one-chip path), by scope."""


def read(r):
    return r.scope_ms(r"stage\d/shift")
