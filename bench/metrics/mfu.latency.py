"""Model step's share of the chip's bf16 peak while it runs, in %: the
operations of the real images of each batch over the ``fn`` spans (model
call to ``block_until_ready``) of the untraced part of the window.  Padding
rows count as no work.  Layer: the program's jitted model function."""


def read(r):
    b = r.batches_in(r.untraced)
    busy = sum(x["fn"][1] - x["fn"][0] for x in b)
    if not busy:
        return None
    flops = sum(x["n"] for x in b) * r.flops_per_image()
    return 100.0 * flops / busy / r.peak["bf16_flops"]
