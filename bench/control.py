"""Readings that set a cell's correctness limit, all in one process: the
program's ``logit_err`` on a dozen seeds, and the control's, served in the
program's place, each through a short window at the cell's own load.  The
control is the reference with float8 e4m3 operands, the step below the
bfloat16 operands the configurations state; ``--controls bf16,fp8`` also
reads the reference computed in bfloat16 throughout.

    python3 bench/control.py --workload vgg16-b1 --seed 1000 --seeds 12 \\
        --control-seeds 3 --seconds 3

Prints one line per run and, last, a JSON object of the readings.  It runs
on the chip, like ``run.py``; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys

import run


def control(ref, model, mode):
    """A ``swap`` that serves the reference in ``mode`` instead of the program."""
    import jax

    return lambda _program: jax.jit(functools.partial(ref.forward, m=model, mode=mode))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True, help="first seed; the rest follow it")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    spec = run.Spec()
    cell = spec.cell(args.workload)
    peaks = spec.peaks()
    sys.path.insert(0, str(spec.root / "src"))
    try:
        dev = run.device_info(peaks, int(cell["chips"]))
    except run.BenchError as e:
        run.log(f"control: {e}")
        return 2
    conf = spec.config(cell["config"])
    ref = spec.reference(conf["arch"])
    out = {"workload": args.workload, "program": {}}
    jobs = [("program", None, args.seeds)] + [
        (mode, control(ref, conf["model"], mode), args.control_seeds)
        for mode in args.controls.split(",") if mode]
    for name, swap, n in jobs:
        out.setdefault(name, {})
        for seed in range(args.seed, args.seed + n):
            line, e2e = run.execute(spec, args.workload, seed, args.seconds, False, dev,
                                    peaks[dev["kind"]], swap=swap)
            err = line["checks"]["logit_err"]["value"]
            out[name][seed] = err
            print(f"{name} seed {seed}: logit_err {err!r}, {line['attempted']} requests, "
                  f"correct {line['correct']}", flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
