"""Serving launcher: HALP-partitioned VGG-16 (the paper's workload) or any
vision arch, through the deadline-aware batching engine, at the arch's
published size (``--smoke`` serves the reduced CPU-sized config instead).

    PYTHONPATH=src python -m repro.launch.serve --arch vgg16 --requests 32
    PYTHONPATH=src python -m repro.launch.serve --arch vgg16 --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch vit-l16 --requests 16

After the drain it prints each span's count, mean and max (set-up:
``build.init``, ``build.plan``; per batch: ``serve.step`` and its children
``serve.stack``, ``serve.call``, ``serve.split``; see
``repro.runtime.tracing``).  Run under ``jax.profiler`` the same spans appear
on the host plane of the trace.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import time

import jax
import jax.numpy as jnp

from repro.configs import get
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import device_summary
from repro.runtime.serve import BatchingEngine, ServeConfig
from repro.runtime.tracing import SpanLog


def _is_kernel(path) -> bool:
    return getattr(path[-1], "key", None) == "w"


@jax.jit
def _bf16_kernels(params):
    """``params`` with every leaf under the key ``"w"`` cast to bfloat16 (one
    program for the whole tree, not one per kernel shape)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a.astype(jnp.bfloat16) if _is_kernel(path) else a, params)


def build_model(arch_name: str, *, smoke: bool = False, seed: int = 0,
                spans: SpanLog | None = None):
    """``(cfg, params, fn)``: the arch's config (published, or the smoke
    config), random weights from ``seed``, and the batch -> logits function
    the engine serves.  VGG-16 runs through the HALP plan (``plan_halp`` +
    ``run_plan``) and then its classifier head.  The weights are an argument
    of the jitted function, not constants folded into the executable (VGG-16's
    are 0.5 GB, which would bloat the compile and the compile cache).
    Every conv and dense kernel (each leaf under the key ``"w"``) is kept in
    bfloat16 from here on: at default precision the chip computes these
    products with bf16 operands and f32 accumulation, the configurations'
    stated operand type, so an f32 kernel would be rounded again on every
    call; ``layers.conv2d`` and ``layers.dense`` take it as kept.  Biases,
    norms, embeddings and tables stay f32.
    ``spans`` records the random init, to its last array, as ``build.init``
    (its ``info``: ``operand_bytes`` kept in bf16, ``weight_bytes`` of all
    parameters) and the plan as ``build.plan``."""
    def span(name):
        return contextlib.nullcontext() if spans is None else spans.span(name)

    arch = get(arch_name)
    cfg = arch.smoke_cfg if smoke else arch.cfg
    with span("build.init") as info:
        params = _bf16_kernels(arch.module.init(jax.random.PRNGKey(seed), cfg))
        if spans is not None:
            jax.block_until_ready(params)
            leaves = jax.tree_util.tree_leaves_with_path(params)
            info["operand_bytes"] = sum(a.nbytes for path, a in leaves if _is_kernel(path))
            info["weight_bytes"] = sum(a.nbytes for _, a in leaves)

    if arch_name == "vgg16":
        from repro.core import plan_halp
        from repro.models import vgg
        from repro.spatial import run_plan

        with span("build.plan"):
            plan = plan_halp(cfg.geom(), overlap_rows=4)

        def model(params, batch):
            feats = run_plan(plan, params["features"], vgg.apply_layer, batch)
            return vgg.head(params, feats)
    else:
        def model(params, batch):
            return arch.module.apply(params, cfg, batch)

    return cfg, params, functools.partial(jax.jit(model), params)


def serve(fn, cfg, *, requests: int, max_batch: int, deadline_s: float,
          seed: int = 1, observer=None, spans: SpanLog | None = None) -> BatchingEngine:
    """Submit ``requests`` random images (from ``seed``) and drain them
    through a :class:`BatchingEngine` around ``fn``; returns the engine, whose
    ``completed`` requests carry their payloads and results."""
    eng = BatchingEngine(fn, ServeConfig(max_batch=max_batch), observer=observer,
                         spans=spans)
    res = cfg.img_res
    key = jax.random.PRNGKey(seed)
    for _ in range(requests):
        key, k = jax.random.split(key)
        eng.submit(jax.random.normal(k, (res, res, cfg.in_channels)),
                   deadline_s=deadline_s)
    eng.run_until_drained()
    return eng


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vgg16")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=500.0)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's reduced config (CPU examples, tests)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    dev = device_summary()
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']}")
    spans = SpanLog()
    cfg, _params, fn = build_model(args.arch, smoke=args.smoke, spans=spans)
    print(f"serving {args.arch} at {cfg.img_res} px"
          + (" through the HALP plan" if args.arch == "vgg16" else ""))

    t0 = time.monotonic()
    eng = serve(fn, cfg, requests=args.requests, max_batch=args.max_batch,
                deadline_s=args.deadline_ms / 1e3, spans=spans)
    wall = time.monotonic() - t0
    stats = eng.stats()
    print(f"requests={stats['completed']} deadline_met={stats['deadline_met_frac']:.3f} "
          f"p50={stats['p50_latency_s']*1e3:.1f}ms p99={stats['p99_latency_s']*1e3:.1f}ms "
          f"throughput={stats['completed']/wall:.1f} req/s")
    by_name: dict[str, list[float]] = {}
    for s in spans.spans:
        by_name.setdefault(s.name, []).append((s.t1 - s.t0) * 1e3)
    for name, ms in by_name.items():
        print(f"span {name}: n={len(ms)} mean={sum(ms) / len(ms):.3f}ms max={max(ms):.3f}ms")
    return stats


if __name__ == "__main__":
    main()
