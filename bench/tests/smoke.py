"""A test-only copy of the benchmark at the architectures' smoke sizes, for
runs of every cell's control flow on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
# the program's reduced configurations (repro.configs.*.SMOKE)
SMOKE_MODELS = {
    "vgg16-224": {"img_res": 64, "in_channels": 3, "num_classes": 10, "width_mult": 0.125,
                  "blocks": [[2, 64], [2, 128], [3, 256], [3, 512], [3, 512]],
                  "fc_dims": [4096, 4096]},
    "vit-l16-224": {"img_res": 64, "in_channels": 3, "num_classes": 10, "patch": 8,
                    "n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128},
}


def make_root(tmp: Path) -> tuple[Path, Path]:
    """``(root, bench)``: the real ``BENCHMARK.json`` and data files, with
    every configuration swapped for its smoke size."""
    bench = tmp / "bench"
    for d in ("counts", "reference", "traffic", "metrics"):
        shutil.copytree(BENCH / d, bench / d)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    for name, model in SMOKE_MODELS.items():
        conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        conf.update(model=model, smoke=True)
        (bench / "configs" / f"{name}.json").write_text(json.dumps(conf))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return tmp, bench


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def peak() -> dict:
    return json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
