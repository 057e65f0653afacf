"""The model program's share of its roofline, in %: for each model program
run wholly inside the traced window, the least time the chip needs for it
at the executed width (``Readings.min_time_s``: the larger of the matrix
layers' operations over the bf16 peak and the unavoidable bytes over HBM
bandwidth), summed, over the device time of all the ops of those runs.
The whole program is the denominator because XLA's conv and dot fusions
cannot be told apart from their neighbours: at batch 1 the dense layers
become multiply-reduce fusions on the vector unit.  Layer: kernels (the XLA
ops of the one-chip path)."""


def read(r):
    t, mod = r.trace, r.model_module
    if not t or not mod:
        return None
    runs = t["runs"].get(mod, 0)
    busy = sum(v for k, v in t["op_s"].items() if k.startswith(f"{mod}:"))
    if not runs or not busy:
        return None
    return 100.0 * runs * r.min_time_s(r.width) / busy
