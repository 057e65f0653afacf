"""Swin's window attention's share of its roofline, in %: the least time
the chip needs for the ``window_attn`` scopes of one model run at the
executed width (the larger of their operations over the bf16 peak and the
bytes no schedule avoids over HBM bandwidth, ``counts.window_attn``), over
their device time per run (``window_attn_ms``).  Layer: kernels (the XLA ops
of the one-chip path), by scope."""


def read(r):
    ms = r.scope_ms(r"stage\d/window_attn")
    if not ms:
        return None
    flops, nbytes = r.counts.window_attn(r.model, r.width, r.act_bytes, r.w_bytes)
    least = max(flops / r.peak["bf16_flops"], nbytes / r.peak["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
