"""Device time per model program run, in ms, of the ops in the program
scope ``head`` and its children (VGG-16's three dense layers,
``head/fc1``-``fc3``), over the runs wholly inside the traced window.
Layer: kernels (the XLA ops of the one-chip path), by scope."""


def read(r):
    return r.scope_ms("head")
