"""Device time per model program run, in ms, of the ops that only move data
(copies, slices, pads, concatenations, transposes, casts; ``move`` in
``bench/xplane.py``), over the runs wholly inside the traced window.
Layer: kernels, data movement (``run_plan``'s slices, pads and
concatenations, and XLA's layout copies)."""


def read(r):
    t, mod = r.trace, r.model_module
    if not t or not mod or not t["runs"].get(mod):
        return None
    return t["op_s"].get(f"{mod}:move", 0.0) / t["runs"][mod] * 1e3
