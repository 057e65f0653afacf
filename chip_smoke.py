"""Smoke run of the system's main path on a TPU, at VGG-16's published size.

    python chip_smoke.py               # one chip: serve full-width VGG-16
    python chip_smoke.py --four-chips  # four chips: the HALP mesh program

One chip: serves a few 224-px requests through ``repro.launch.serve``'s own
path (``plan_halp`` -> jitted ``run_plan`` -> ``vgg.head`` behind the
``BatchingEngine``) and compares the served logits with plain ``vgg.apply``
on the same weights and inputs.  Four chips: runs the 13-conv / 5-pool
VGG-16 feature stack as one ``shard_map`` program over a 4-way spatial mesh
in the capacity-weighted padded layout, every conv through the fused Pallas
halo kernel, and compares it with ``vgg.features`` on one chip.

Everything runs in this one process.  Weights and inputs come from
``--seed``.  Exits non-zero, with no result line, unless JAX's first device
is a TPU and every check passes.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Timings printed before it are set-up diagnostics, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# the TPU library otherwise writes its driver logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

N_REQUESTS = 8
MAX_BATCH = 4
FOUR_CHIP_BATCH = 2

# Bounds on max|got - ref| / max|ref|.  On a TPU, f32 matmuls and convs at
# the default precision round their operands to bf16 (unit roundoff 2^-9 ~=
# 2e-3) and accumulate in f32.
#
# Served path vs vgg.apply: both run the same lax convs at the same
# precision and differ only in which rows each conv sees; on the chip they
# agree bit for bit (0.0).  One input row zeroed in one segment at any one
# conv read 1.35e-2 to 1.6e-1 at the logits (f32 on the host, PERF.md): a row
# wrong early is diluted by the layers after it.  5e-3, about two bf16
# roundoffs of the largest logit, sits 2.7x below the smallest such fault.
SERVE_REL_BOUND = 5e-3
# Mesh program vs vgg.features, compared at the end of each of the five
# blocks.  The Pallas kernel and XLA's conv both round their operands to
# bf16, but a 1e-7 difference in one layer's output flips the rounding of some
# of the next layer's operands, and the flips compound: 5.9e-3 after 13 convs
# on four chips (1e-7 for one conv alone).  A halo dropped on one shard is
# diluted by every layer after it (2.4e-2 after 13 convs, when dropped at
# conv1_1), so each block's output is checked on its own.  Dropping one
# shard's top or bottom halo at any one of the 13 convs read 1.2e-1 to 5.4e-1
# at the end of that conv's block (f32 on host devices, PERF.md).  2e-2 sits
# 3x above the noise and 6x below the smallest fault.
MESH_REL_BOUND = 2e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def rel_diff(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        fail(f"shape {got.shape} != reference shape {want.shape}")
    if not np.isfinite(got).all():
        fail("non-finite values in the output")
    return float(np.abs(got - want).max() / np.abs(want).max())


class CacheEvents:
    """Counts JAX persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self) -> str:
        return f"compile cache hits={self.hits} misses={self.misses}"


def serve_phase(seed: int, cache: CacheEvents) -> None:
    """Serve full-width VGG-16 requests through the launcher's own path."""
    from repro.launch.serve import build_model, serve
    from repro.models import vgg

    cfg, params, fn = build_model("vgg16", seed=seed)
    print(f"serve: vgg16 at {cfg.img_res} px, widths x{cfg.width_mult}, "
          f"{cfg.num_classes} classes")

    t0 = time.perf_counter()
    warm = jnp.zeros((MAX_BATCH, cfg.img_res, cfg.img_res, cfg.in_channels))
    jax.block_until_ready(fn(warm))
    print(f"serve: compile+first batch {time.perf_counter() - t0:.1f} s ({cache})")

    widths: list[int] = []
    eng = serve(fn, cfg, requests=N_REQUESTS, max_batch=MAX_BATCH,
                deadline_s=60.0, seed=seed + 1,
                observer=lambda width, _dt: widths.append(width))
    stats = eng.stats()
    if stats["completed"] != N_REQUESTS:
        fail(f"served {stats['completed']} of {N_REQUESTS} requests")
    print(f"serve: {stats['completed']} requests in {len(widths)} batches of "
          f"{widths}; per-request p50={stats['p50_latency_s'] * 1e3:.1f} ms "
          f"p99={stats['p99_latency_s'] * 1e3:.1f} ms")

    done = sorted(eng.completed, key=lambda r: r.rid)
    x = jnp.stack([r.payload for r in done])
    got = jnp.stack([r.result for r in done])
    want = jax.jit(vgg.apply, static_argnums=1)(params, cfg, x)
    rel = rel_diff(got, want)
    print(f"serve: logits {tuple(got.shape)} vs vgg.apply: max|diff|/max|ref| "
          f"= {rel:.3e} (bound {SERVE_REL_BOUND:.0e})")
    if rel > SERVE_REL_BOUND:
        fail(f"served logits differ from vgg.apply by {rel:.3e}")


def mesh_features(cfg, mesh):
    """``(program, heights, block_heights)``: the VGG feature stack of
    ``cfg`` as one jitted ``shard_map`` program over ``mesh``'s ``"sp"`` axis
    that returns the output of every block (every pool), with the input rows
    laid out by ``shard_heights`` (stride-aligned, equal ratios) in the padded
    weighted-shard form; ``block_heights[b]`` are block ``b``'s output rows
    per shard."""
    from jax.sharding import PartitionSpec as P

    from repro.models.layers import relu
    from repro.spatial import (
        conv2d_spatial,
        max_pool_spatial,
        shard_heights,
        spatial_alignment,
    )

    geom = cfg.geom()
    hts = shard_heights(cfg.img_res, mesh.shape["sp"], align=spatial_alignment(geom))

    def features(xs, feats):
        h, blocks = hts, []
        for p_l, g in zip(feats, geom.layers):
            if g.kind == "pool":
                xs = max_pool_spatial(xs, g.k, g.s, axis_name="sp", heights=h)
            else:
                xs = relu(conv2d_spatial(xs, p_l, g.k, g.s, g.p, axis_name="sp",
                                         overlap=True, engine="pallas", heights=h))
            h = tuple(r // g.s for r in h)
            if g.kind == "pool":
                blocks.append(xs)
        return tuple(blocks)

    block_hts, h = [], hts
    for g in geom.layers:
        h = tuple(r // g.s for r in h)
        if g.kind == "pool":
            block_hts.append(h)
    rows = P(None, "sp", None, None)
    program = jax.jit(jax.shard_map(features, mesh=mesh, in_specs=(rows, P()),
                                    out_specs=(rows,) * len(block_hts),
                                    check_vma=False))
    return program, hts, block_hts


def reference_blocks(params, cfg, x) -> list:
    """``vgg.features`` on one device, up to the end of each block."""
    from repro.models import vgg

    ends = [i + 1 for i, g in enumerate(cfg.geom().layers) if g.kind == "pool"]
    ref = jax.jit(vgg.features, static_argnums=1)
    return [ref({"features": params["features"][:e]}, cfg, x) for e in ends]


def block_diffs(blocks, block_hts, want) -> list[float]:
    """max|diff|/max|ref| of each block's mesh output against ``want``."""
    from repro.spatial import merge_padded_shards

    return [rel_diff(merge_padded_shards(np.asarray(y), h), r)
            for y, h, r in zip(blocks, block_hts, want)]


def four_chip_phase(seed: int, cache: CacheEvents) -> None:
    """The VGG-16 feature stack as one 4-way spatial mesh program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get
    from repro.launch.mesh import make_spatial_mesh
    from repro.models import vgg
    from repro.spatial import to_padded_shards

    n = 4
    if len(jax.devices()) < n:
        fail(f"--four-chips needs {n} devices, found {len(jax.devices())}")
    cfg = get("vgg16").cfg
    mesh = make_spatial_mesh(n)
    program, hts, block_hts = mesh_features(cfg, mesh)
    n_convs = sum(g.kind != "pool" for g in cfg.geom().layers)
    print(f"mesh: vgg16 features at {cfg.img_res} px, {n_convs} convs, "
          f"shard heights {hts} -> {block_hts[-1]}")

    params = vgg.init(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (FOUR_CHIP_BATCH, cfg.img_res, cfg.img_res, cfg.in_channels))
    xs = jax.device_put(to_padded_shards(x, hts),
                        NamedSharding(mesh, P(None, "sp", None, None)))
    feats = jax.device_put(params["features"], NamedSharding(mesh, P()))

    t0 = time.perf_counter()
    compiled = program.lower(xs, feats).compile()
    print(f"mesh: compile {time.perf_counter() - t0:.1f} s ({cache})")
    n_kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    print(f"mesh: {n_kernels} tpu_custom_call kernels for {n_convs} convs")
    if n_kernels != n_convs:
        fail(f"{n_kernels} fused kernels for {n_convs} convs: a conv left the "
             f"Pallas engine")

    blocks = compiled(xs, feats)
    jax.block_until_ready(blocks)
    for name, arr in (("input", xs), ("output", blocks[-1])):
        devs = {s.device.id for s in arr.addressable_shards}
        if len(devs) != n:
            fail(f"{name} shards sit on {len(devs)} device(s) {sorted(devs)}, "
                 f"not {n}")
    print(f"mesh: input and output shards on devices "
          f"{sorted(s.device.id for s in blocks[-1].addressable_shards)}")

    one = jax.devices()[0]
    want = reference_blocks(jax.device_put(params, one), cfg, jax.device_put(x, one))
    rels = block_diffs(blocks, block_hts, want)
    print(f"mesh: features {tuple(want[-1].shape)} vs one-chip vgg.features, "
          f"max|diff|/max|ref| at the end of each block: "
          f"{', '.join(f'{r:.3e}' for r in rels)} (bound {MESH_REL_BOUND:.1e})")
    if max(rels) > MESH_REL_BOUND:
        fail(f"mesh features differ from vgg.features by {max(rels):.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip HALP mesh program and its reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import device_summary

    device = device_summary()
    if device["platform"] != "tpu":
        fail(f"no TPU: JAX brought up {device}")
    print(f"device: {device}")
    print(f"compile cache: {enable_compile_cache()}")
    cache = CacheEvents()
    if args.four_chips:
        four_chip_phase(args.seed, cache)
    else:
        serve_phase(args.seed, cache)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
