import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on the
production meshes and extract the roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --cell train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all             # 40 cells, single-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod # 2-pod pass

Results (memory analysis, FLOPs/bytes, per-collective byte totals) are cached
as JSON under benchmarks/dryrun_results/ -- benchmarks/roofline.py renders the
EXPERIMENTS.md tables from them.
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get, list_archs
from repro.configs.steps import build
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.parallel.sharding import (
    input_shardings,
    param_shardings,
    state_shardings,
)
from jax.sharding import NamedSharding, PartitionSpec as P

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def _shape_bytes(type_str: str) -> int:
    """Sum byte sizes of every dtype[dims] occurrence in an HLO type string."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective result-shape bytes (per device), summed over all call
    sites.  ``-start`` variants are counted; their ``-done`` twins are not."""
    per = {c: 0 for c in _COLLECTIVES}
    count = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.lstrip()
        if not (s.startswith("%") or s.startswith("ROOT")):
            continue
        if "-done" in s:
            continue
        m = re.search(r"=\s+(\([^)]*\)|\S+)\s+([\w-]+)", s)
        if not m:
            continue
        op = m.group(2)
        base = op[:-6] if op.endswith("-start") else op
        if base in per:
            per[base] += _shape_bytes(m.group(1))
            count[base] += 1
    per["total"] = sum(per[c] for c in _COLLECTIVES)
    per["counts"] = count
    return per


def _out_shardings(bundle, arch, cell, mesh, state_sh, in_sh):
    dp = dp_axes(mesh)
    dpx = dp if len(dp) > 1 else dp[0]

    def rep():
        return NamedSharding(mesh, P())

    if bundle.kind == "train":
        metrics = jax.eval_shape(bundle.fn, bundle.state, *bundle.input_list)[1]
        return (state_sh, jax.tree_util.tree_map(lambda _: rep(), metrics))
    if bundle.kind == "prefill":
        return NamedSharding(mesh, P(None, None, "model"))
    if bundle.kind == "decode":
        logits = NamedSharding(mesh, P(None, "model"))
        return (logits, in_sh["cache"])
    if bundle.kind == "gen":
        return in_sh["latents"]
    if bundle.kind == "serve":
        return NamedSharding(mesh, P())
    return None


def run_cell(
    arch_name: str,
    cell_name: str,
    multi_pod: bool,
    verbose: bool = True,
    variant: str = "base",
) -> dict:
    from repro.parallel import hints
    from repro.parallel.variants import set_variant

    v = set_variant(variant)
    arch = get(arch_name)
    cell = arch.cells[cell_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch_name,
        "cell": cell_name,
        "mesh": mesh_name,
        "family": arch.family,
        "variant": variant,
    }
    if cell.skip:
        rec["status"] = "skipped"
        rec["skip_reason"] = cell.skip
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = dp_axes(mesh)
    dpx = dp if len(dp) > 1 else dp[0]
    # NOTE: anchoring the MoE dispatch boundary (moe_tokens/moe_slots hints)
    # was measured and REFUTED -- GSPMD implemented the forced reshard worse
    # than its own choice (deepseek bound 194 s -> 302 s); the hint names stay
    # in the model as no-ops.  See EXPERIMENTS.md §Perf iteration 3.
    if v.seq_shard_activations:
        hints.set_rules(
            {"lm_residual": NamedSharding(mesh, P(dpx, "model", None))}
        )
    elif v.constrain_residual:
        hints.set_rules(
            {"lm_residual": NamedSharding(mesh, P(dpx, None, None))}
        )
    else:
        hints.clear_rules()
    bundle = build(arch, cell_name)
    in_sh = input_shardings(bundle.inputs, arch, cell, mesh)
    if bundle.kind == "train":
        state_sh = state_shardings(bundle.state, arch, mesh)
    else:
        state_sh = param_shardings(bundle.state, arch, mesh)
    out_sh = _out_shardings(bundle, arch, cell, mesh, state_sh, in_sh)

    jitted = jax.jit(
        bundle.fn,
        in_shardings=(state_sh, *[in_sh[k] for k in bundle.inputs]),
        out_shardings=out_sh,
        donate_argnums=(0,) if bundle.donate_state else (),
    )
    with mesh:
        lowered = jitted.lower(bundle.state, *bundle.input_list)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    rec["lower_s"] = round(t_lower, 2)
    rec["compile_s"] = round(t_compile, 2)

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}

    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        rec["cost"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", cost.get("bytes_accessed", 0.0))),
        }
    except Exception as e:  # pragma: no cover
        rec["cost"] = {"error": str(e)}

    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["hlo_bytes"] = len(hlo)
    # while-trip-corrected accounting (XLA's cost_analysis counts scanned layer
    # stacks once; see repro.launch.hlo_cost) -- the roofline source of truth.
    hc = analyze_hlo(hlo)
    rec["hlo_cost"] = {
        "flops": hc.flops,
        "bytes_accessed": hc.bytes_accessed,
        "collective_bytes": hc.collective_bytes,
        "per_collective": hc.per_collective,
        "collective_counts": hc.collective_counts,
        "unknown_trip_whiles": hc.unknown_trip_whiles,
    }
    try:  # archive compressed HLO for offline perf iteration
        import zstandard as zstd

        hdir = RESULTS_DIR / "hlo"
        hdir.mkdir(parents=True, exist_ok=True)
        sfx = "" if rec.get("variant", "base") == "base" else f"__{rec['variant']}"
        name = f"{rec['arch']}__{rec['cell']}__{rec['mesh']}{sfx}.hlo.zst"
        (hdir / name).write_bytes(zstd.ZstdCompressor(level=6).compress(hlo.encode()))
    except Exception:
        pass
    rec["status"] = "ok"

    if verbose:
        print(f"--- {arch_name} / {cell_name} / {mesh_name} ---")
        print(f"lower {t_lower:.1f}s  compile {t_compile:.1f}s")
        print("memory_analysis:", rec["memory"])
        print("cost_analysis:", rec["cost"])
        print("collective bytes/device:", {k: v for k, v in rec["collectives"].items() if k != "counts"})
    return rec


def save(rec: dict):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    suffix = "" if rec.get("variant", "base") == "base" else f"__{rec['variant']}"
    name = f"{rec['arch']}__{rec['cell']}__{rec['mesh']}{suffix}.json"
    (RESULTS_DIR / name).write_text(json.dumps(rec, indent=2))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-cached", action="store_true")
    ap.add_argument("--variant", default="base")
    args = ap.parse_args()

    assigned = [a for a in list_archs() if a != "vgg16"]
    targets = []
    if args.all:
        for a in assigned:
            for c in get(a).cells:
                targets.append((a, c))
    else:
        cells = [args.cell] if args.cell else list(get(args.arch).cells)
        targets = [(args.arch, c) for c in cells]

    failures = []
    for a, c in targets:
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        suffix = "" if args.variant == "base" else f"__{args.variant}"
        cache = RESULTS_DIR / f"{a}__{c}__{mesh_name}{suffix}.json"
        if args.skip_cached and cache.exists():
            st = json.loads(cache.read_text()).get("status")
            if st in ("ok", "skipped"):
                print(f"cached: {a}/{c}/{mesh_name} ({st})")
                continue
        try:
            rec = run_cell(a, c, args.multi_pod, variant=args.variant)
        except Exception as e:
            rec = {
                "arch": a, "cell": c,
                "mesh": mesh_name,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-3000:],
            }
            print(f"ERROR {a}/{c}: {e}")
            failures.append((a, c))
        save(rec)
    if failures:
        print("FAILURES:", failures)
        return 1
    print("dry-run complete: all cells ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
