"""Engine host time per batch, in ms: the mean over the batches of the
untraced part of the window of the ``engine.step`` span less the ``fn``
span inside it (stacking the payloads, the EDF heap, slicing each request's
result).  Layer: ``runtime/serve.BatchingEngine``."""


def read(r):
    b = r.batches_in(r.untraced)
    if not b:
        return None
    host = sum((x["step"][1] - x["step"][0]) - (x["fn"][1] - x["fn"][0]) for x in b)
    return host / len(b) * 1e3
