"""Device time per model program run, in ms, of the ops in every program
scope ``layer<i>/exchange``: HALP's boundary exchange between the slots of
the served plan (``run_plan``'s concatenations of neighbours' rows), over
the runs wholly inside the traced window.  Layer: kernels (the XLA ops of
the one-chip path), by scope."""


def read(r):
    return r.scope_ms(r"layer\d+/exchange")
