"""Model step's share of the chip's bf16 peak over the untraced part of the
window, in %: images completed there per second times the operations of one
image.  Layer: the program's jitted model function."""


def read(r):
    t0, t1 = r.untraced
    n = sum(1 for q in r.requests if q.done is not None and t0 <= q.done <= t1)
    if not n or t1 <= t0:
        return None
    return 100.0 * n / (t1 - t0) * r.flops_per_image() / r.peak["bf16_flops"]
