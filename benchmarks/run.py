"""Benchmark entry point: one function per paper table/figure and the
overlap ablation.

    PYTHONPATH=src python -m benchmarks.run

Prints human-readable tables interleaved with ``name,us_per_call,derived`` CSV
rows (the scaffold contract).
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    from benchmarks import ablation_overlap, paper_tables

    print("#" * 72)
    print("# HALP paper reproduction (Li, Iosifidis, Zhang 2022)")
    print("#" * 72)
    paper_tables.run_all()
    ablation_overlap.run()


if __name__ == "__main__":
    main()
