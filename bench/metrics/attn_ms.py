"""Device time per model program run, in ms, of the ops in the program
scope ``attn`` (ViT's self-attention: LayerNorm, the QKV and output
projections, scores, softmax and the residual add), over the runs wholly
inside the traced window.  Layer: kernels (the XLA ops of the one-chip
path), by scope."""


def read(r):
    return r.scope_ms("attn")
