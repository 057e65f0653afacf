"""A test-only copy of the benchmark at the architectures' smoke sizes, for
runs of every cell's control flow on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def smoke_model(conf: dict) -> dict:
    """A configuration's ``model`` keys at its architecture's smoke size, as
    the program's registry gives it (``repro.configs.get(arch).smoke_cfg``)."""
    from repro.configs import get

    cfg = get(conf["arch"]).smoke_cfg
    return {k: json.loads(json.dumps(getattr(cfg, k))) for k in conf["model"]}


def make_root(tmp: Path, bench_src: Path = BENCH) -> tuple[Path, Path]:
    """``(root, bench)``: ``BENCHMARK.json`` beside ``bench_src`` and its data
    files, with every configuration in ``bench_src/configs`` swapped for its
    smoke size."""
    bench = tmp / "bench"
    for d in ("counts", "reference", "traffic", "metrics"):
        shutil.copytree(bench_src / d, bench / d)
    shutil.copy(bench_src / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    for path in sorted((bench_src / "configs").glob("*.json")):
        conf = json.loads(path.read_text())
        conf.update(model=smoke_model(conf), smoke=True)
        (bench / "configs" / path.name).write_text(json.dumps(conf))
    shutil.copy(bench_src.parent / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return tmp, bench


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def peak() -> dict:
    return json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
