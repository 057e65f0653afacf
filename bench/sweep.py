"""The knee of an open-loop traffic mix on a configuration: serves it at
each offered rate in turn, in one process, and prints what each rate
sustained.

    python3 bench/sweep.py --config vgg16-224 --traffic poisson3_b8 --seed 5 \\
        --rates 300,400,500 --seconds 10

A rate is sustained when the queue at the window's close holds no more than
two batches; the knee is the highest rate sustained.  Prints one line per
rate and, last, a JSON object of the readings.  It runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="offered rates in requests/s, comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = run.Spec()
    cell = {"name": f"{args.config}.{args.traffic}", "config": args.config,
            "traffic": args.traffic, "chips": 1}
    peaks = spec.peaks()
    sys.path.insert(0, str(spec.root / "src"))
    try:
        dev = run.device_info(peaks, int(cell["chips"]))
    except run.BenchError as e:
        run.log(f"sweep: {e}")
        return 2
    rows = []
    for rate in (float(x) for x in args.rates.split(",")):
        line, e2e = run.execute(spec, cell, args.seed, args.seconds, False, dev,
                                peaks[dev["kind"]], mix={"rate_hz": rate})
        max_batch = int(spec.mix(cell)["max_batch"])
        row = {"rate_hz": rate, "sustained": e2e["backlog_at_close"] <= 2 * max_batch,
               "correct": line["correct"], **e2e}
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = max((r["rate_hz"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"config": args.config, "traffic": args.traffic, "knee_hz": knee,
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
