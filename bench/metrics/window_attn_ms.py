"""Device time per model program run, in ms, of the ops in the program
scopes ``stage<i>/window_attn`` (Swin's window attention: LayerNorm, window
partition and reverse, the QKV and output projections, scores, the
relative-position bias, the shifted-window mask, softmax, values and the
residual add), over the runs wholly inside the traced window.  Layer:
kernels (the XLA ops of the one-chip path), by scope."""


def read(r):
    return r.scope_ms(r"stage\d/window_attn")
