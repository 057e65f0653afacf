"""Tests of the benchmark's Swin-B 384 px configuration on the CPU: its smoke
size from the registry, its counts against the program and XLA, its
fp8 control, its readers' arithmetic, and a trace of its cell recorded on
the chip.

    python -m pytest bench/tests/test_swin_b_384.py
"""
from __future__ import annotations

import functools
import gzip
import json
import re

import pytest

from test_bench import (  # noqa: F401  (spec is a fixture)
    BENCH, RECORDED, _assert_scopes_sum_to_classes, _readings, _recorded, run, serve, smoke,
    spec, xplane)

CELL = "swin-b-384-b8-saturate"
MODEL = json.loads((BENCH / "configs" / "swin-b-384.json").read_text())["model"]


def _counts():
    return run.load_py(BENCH / "counts" / "swin-b-384.py")


def test_swin_smoke_model_comes_from_the_registry():
    conf = json.loads((BENCH / "configs" / "swin-b-384.json").read_text())
    assert smoke.smoke_model(conf) == {
        "img_res": 64, "in_channels": 3, "num_classes": 10, "patch": 4, "window": 4,
        "depths": [2, 2], "dims": [32, 64], "n_heads": [2, 4], "mlp_ratio": 4}


def test_swin_parameter_count_matches_the_program():
    import jax

    from repro.configs import get

    arch = get("swin-b-384")
    shapes = jax.eval_shape(lambda k: arch.module.init(k, arch.cfg), jax.random.PRNGKey(0))
    assert _counts().params(MODEL) == sum(a.size for a in jax.tree.leaves(shapes))


def test_swin_fp8_control_is_not_correct(spec):  # noqa: F811
    import jax

    conf = spec.config(spec.cell(CELL)["config"])
    ref = spec.reference(conf["arch"])
    line = serve(spec, CELL, swap=lambda _p: jax.jit(
        functools.partial(ref.forward, m=conf["model"], mode="fp8")))
    assert line["correct"] is False


def test_swin_counts_match_xla_cost_analysis():
    import jax
    import jax.numpy as jnp

    from repro.models import swin

    # two blocks, one shifted, per stage at the published head width 32: no
    # stage is deep enough to scan, whose body XLA's cost analysis counts once
    m = {"img_res": 96, "in_channels": 3, "num_classes": 1000, "patch": 4, "window": 6,
         "depths": [2, 2, 2], "dims": [64, 128, 256], "n_heads": [2, 4, 8], "mlp_ratio": 4}
    cfg = swin.SwinConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in m.items()})
    params = jax.eval_shape(lambda k: swin.init(k, cfg), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 96, 96, 3), jnp.float32)
    cost = jax.jit(lambda p, x: swin.apply(p, cfg, x)).lower(params, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    counts = _counts()
    ours = sum(f for _, f, _ in counts.layers(m, 2))
    # XLA also counts LayerNorm, softmax, GELU, the bias and mask adds
    assert 0.9 * cost["flops"] <= ours <= cost["flops"]
    assert counts.flops_per_image(m) == pytest.approx(ours / 2)
    attn = [f for n, f, _ in counts.layers(m, 2)
            if n.split(".")[-1] in ("qkv", "scores", "values", "out")]
    assert len(attn) == 4 * 6 and counts.window_attn(m, 2)[0] == pytest.approx(sum(attn))


def test_swin_counts_at_384_px():
    """Liu et al., Table 1: Swin-B at 384 px, 88M parameters, 47.0G
    multiply-adds (ours 47.08G, which counts the patch projection and head)."""
    counts = _counts()
    assert counts.params(MODEL) == 87_903_584
    assert counts.flops_per_image(MODEL) == pytest.approx(94.17e9, rel=1e-3)
    flops, nbytes = counts.window_attn(MODEL, 8, 4, 4)
    # projections and the windows' scores and values, 36.4% of the operations
    assert flops / (8 * counts.flops_per_image(MODEL)) == pytest.approx(0.3638, abs=1e-4)
    # every block's input read and output written at 8 images, its
    # projections, biases and 23 x 23 bias table read once, all at 4 bytes
    assert nbytes == sum(
        depth * 4 * (2 * 8 * tokens * c + 4 * c * c + 4 * c + 23 ** 2 * heads)
        for depth, tokens, c, heads in ((2, 9216, 128, 4), (2, 2304, 256, 8),
                                        (18, 576, 512, 16), (2, 144, 1024, 32)))


def test_window_attention_scope_metrics(spec):  # noqa: F811
    r = _readings(spec, CELL, [])
    flops, nbytes = r.counts.window_attn(r.model, 8, 4, 4)
    least = max(flops / smoke.peak()["bf16_flops"], nbytes / smoke.peak()["hbm_bytes_per_s"])
    r.trace = {"runs": {"jit_model": 4}, "op_s": {}, "scope_s": {
        "jit_model:stage0/window_attn": 4 * least / 2, "jit_model:stage2/window_attn": 0.004,
        "jit_model:stage3/window_attn": 4 * least / 2 - 0.004, "jit_model:window_attn": 1.0,
        "jit_model:stage0/shift": 0.0004, "jit_model:stage1/shift": 0.0004,
        "jit_model:stage0/mlp": 1.0, "jit_model:stage0": 1.0}}
    assert spec.reader("window_attn_ms.throughput").read(r) == pytest.approx(least * 1e3)
    assert spec.reader("shift_ms.throughput").read(r) == pytest.approx(0.2)
    assert spec.reader("window_attn_roofline.throughput").read(r) == pytest.approx(100.0)
    r.trace["scope_s"]["jit_model:stage2/window_attn"] += 4 * least
    assert spec.reader("window_attn_roofline.throughput").read(r) == pytest.approx(50.0)
    for k in [k for k in r.trace["scope_s"] if "/window_attn" in k or "/shift" in k]:
        del r.trace["scope_s"][k]
    for metric in ("window_attn_ms", "shift_ms", "window_attn_roofline"):
        assert spec.reader(f"{metric}.throughput").read(r) is None  # nothing to read
    r.trace = None
    assert spec.reader("window_attn_roofline.throughput").read(r) is None


def test_recorded_swin_chip_trace_by_scope(spec):  # noqa: F811
    """A trace recorded on a TPU v5 lite (0.06 s of ``swin-b-384-b8-saturate``,
    which held one whole model run), with the model program's HLO text:
    device time by the program's scopes, read within the range of the
    traced chip runs (PERF.md)."""
    t = _recorded("swin-b-384-b8")
    assert t["runs"]["jit_model"] == 1
    found = {k.partition(":")[2].split("/")[0] for k in t["scope_s"] if k.startswith("jit_model:")}
    assert found == {"patch_embed", "head", "(unscoped)"} | {f"stage{i}" for i in range(4)}
    _assert_scopes_sum_to_classes(t)
    r = _readings(spec, CELL, [], trace=t)
    for metric, (lo, hi) in {
            "window_attn_ms.throughput": (10.200, 10.205), "shift_ms.throughput": (0.5497, 0.5501),
            "window_attn_roofline.throughput": (13.63, 13.64),
            "unscoped_ms.throughput": (1.646, 1.650)}.items():
        assert lo < spec.reader(metric).read(r) < hi


def _dots_by_timed_scope(hlo_text):
    """``(own scope, scope of the op the trace times)`` of every convolution
    and dot of a compiled module: a dot inside a fusion is timed as that
    fusion, under the fusion's scope."""
    comps = xplane._computations(hlo_text)
    timed = xplane.scopes(hlo_text)
    caller, loops = {}, set()
    for body in comps.values():
        for name, op, calls, _ in body:
            for c in calls:
                if op in xplane.NESTING:
                    loops.add(c)  # loop bodies: their ops are timed one by one
                else:
                    caller[c] = name
    run_as_is = loops | (set(comps) - set(caller))  # and the entry computation
    where = {name: c for c, body in comps.items() for name, *_ in body}
    out = []
    for c, body in comps.items():
        for name, op, _, text in body:
            if op in xplane.MXU:
                own = xplane.scope(xplane._OP_NAME.search(text)[1])
                at = name
                while where[at] not in run_as_is:
                    at = caller[where[at]]
                out.append((own, timed[at]))
    return out


def test_recorded_swin_window_attention_dots_are_timed_in_their_scope():
    """Each block's QKV, score, value and output-projection dots lie under
    ``stage<i>/window_attn`` and are timed there, so ``window_attn_roofline``
    counts no operation whose time is booked under another scope."""
    hlo = gzip.decompress((RECORDED / "swin-b-384-b8.hlo.txt.gz").read_bytes()).decode()
    dots = _dots_by_timed_scope(hlo)
    attn = re.compile(r"stage\d/window_attn")
    # four per block: the two unrolled blocks of stages 0, 1 and 3, and the
    # (regular, shifted) pair that stage 2's scan runs nine times
    assert sum(1 for own, _ in dots if attn.fullmatch(own)) == 4 * 2 * 4
    assert all(own == at for own, at in dots if attn.fullmatch(own) or attn.fullmatch(at))
    assert {own.split("/")[-1] for own, _ in dots if not attn.fullmatch(own)} == {
        "mlp", "merge", "patch_embed", "head"}
