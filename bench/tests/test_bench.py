"""Tests of the benchmark on the CPU: the harness's lookup by name, the
traffic generator, the operation counts, the metric arithmetic, the trace
reduction, and whole runs of every cell at the architectures' smoke sizes,
with the timed path sound, broken, or replaced by a lower-precision control.

    python -m pytest bench/tests
"""
from __future__ import annotations

import functools
import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import arrivals  # noqa: E402
import run  # noqa: E402
import smoke  # noqa: E402
import xplane  # noqa: E402

DOC = json.loads((smoke.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in DOC["workloads"]]
SEED = 2**33 + 17  # seeds above 32 bits must work


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp / "jax_cache")
    yield run.Spec(*smoke.make_root(tmp))
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR")
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old


def serve(spec, cell, *, trace=False, swap=None, seconds=1.0, seed=SEED):
    line, _ = run.execute(spec, cell, seed, seconds, trace, smoke.CPU_DEVICE, smoke.peak(),
                          swap=swap)
    return line


# -- whole runs at the smoke size -------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(spec, cell, trace):
    line = serve(spec, cell, trace=bool(trace))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["narrow_operands"]["value"] == 0
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in spec.metrics(cell, kind)}
    if trace:  # the CPU trace has no TPU plane: only span and counter metrics
        want = {m["name"] for m in spec.metrics(cell, kind) if m["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0


def _half_batch(program):
    import jax
    import jax.numpy as jnp

    def f(w, batch):
        n = batch.shape[0]
        out = program(w, batch[: n - n // 2])  # half the batch left out
        return jnp.concatenate([out, out[: n // 2]])
    return jax.jit(f)


def _altered_answer(program):
    import jax

    def f(w, batch):
        out = program(w, batch)
        return out.at[0, 0].add(0.1 * abs(out[0]).max())  # one logit moved by 10%
    return jax.jit(f)


@pytest.mark.parametrize("fault", [_half_batch, _altered_answer])
def test_broken_timed_path_is_not_correct(spec, fault):
    line = serve(spec, "vgg16-b8-saturate", swap=fault)
    assert line["correct"] is False
    assert line["checks"]["logit_err"]["value"] > line["checks"]["logit_err"]["limit"]


def test_operand_types_of_convolutions_and_dots():
    types = dict(xplane.operand_types(HLO))
    assert types == {"convolution.3": ("bf16", "bf16")}
    recorded = gzip.decompress((RECORDED / "vgg16-b8.hlo.txt.gz").read_bytes()).decode()
    found = xplane.operand_types(recorded)
    assert len(found) == 42  # 13 convs in each of 3 slots, 3 dense layers
    assert {t for _, ts in found for t in ts} == {"bf16", "f32"}
    assert all(set(ts) <= run.AT_LEAST["bfloat16"] for _, ts in found)


def _int8_matmul(program):
    import jax
    import jax.numpy as jnp

    def f(w, batch):
        out = program(w, batch)
        q = jax.lax.dot(out.astype(jnp.int8), out.T.astype(jnp.int8),
                        preferred_element_type=jnp.int32)
        return out.at[0, 0].add(1e-9 * q[0, 0])  # an 8-bit matmul on the timed path
    return jax.jit(f)


def test_operands_below_the_stated_precision_are_not_correct(spec):
    line = serve(spec, "vgg16-b1", swap=_int8_matmul)
    assert line["checks"]["logit_err"]["value"] <= line["checks"]["logit_err"]["limit"]
    assert line["checks"]["narrow_operands"]["value"] == 1
    assert line["correct"] is False


@pytest.mark.parametrize("cell", ["vgg16-b8-saturate", "vit-l16-b8-saturate"])
def test_fp8_control_is_not_correct(spec, cell):
    import jax

    conf = spec.config(spec.cell(cell)["config"])
    ref = spec.reference(conf["arch"])
    line = serve(spec, cell, swap=lambda _p: jax.jit(
        functools.partial(ref.forward, m=conf["model"], mode="fp8")))
    assert line["correct"] is False


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


@pytest.mark.parametrize("kind,count,chips,msg", [
    ("TPU v9 imaginary", 1, 1, "not in bench/peaks.json"),
    ("TPU v5 lite", 1, 4, "asks for 4 chips"),
])
def test_device_check(monkeypatch, kind, count, chips, msg):
    import jax

    dev = NS(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda: [dev] * count)
    with pytest.raises(run.BenchError, match=msg):
        run.device_info(json.loads((BENCH / "peaks.json").read_text()), chips)


# -- lookup by name -----------------------------------------------------------

def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root, bench = smoke.make_root(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    (bench / "configs" / "vgg16-tiny.json").write_text(json.dumps(
        {**json.loads((bench / "configs" / "vgg16-224.json").read_text()), "arch": "vgg16"}))
    (bench / "traffic" / "closed2_b2.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2, "max_batch": 2, "max_delay_s": 0.002, "pool": 4,
         "classes": [{"name": "standard", "deadline_s": 1.0, "share": 1.0}]}))
    (bench / "metrics" / "requests_per_batch.py").write_text(
        "def read(r):\n    return len(r.requests) / max(1, len(r.batches))\n")
    (bench / "metrics" / "waits.py").write_text("def read(r):\n    return 1.0\n")
    doc["workloads"].append({"name": "vgg16-tiny-b2", "config": "vgg16-tiny",
                             "traffic": "closed2_b2", "chips": 1, "why": "test"})
    doc["per_layer"] += [
        {"name": "requests_per_batch", "unit": "1", "better": "higher", "source": "program_counter",
         "layer": "engine", "moves": "images_per_s", "workloads": ["vgg16-tiny-b2"]},
        {"name": "waits.tiny", "unit": "1", "better": "lower", "source": "program_span",
         "layer": "engine", "moves": "images_per_s", "workloads": ["vgg16-tiny-b2"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    spec = run.Spec(root, bench)
    cell = spec.cell("vgg16-tiny-b2")
    assert spec.config(cell["config"])["arch"] == "vgg16"
    assert spec.mix(cell)["clients"] == 2
    names = [m["name"] for m in spec.metrics("vgg16-tiny-b2", "per_layer")]
    assert names == ["requests_per_batch", "waits.tiny"]
    readings = NS(requests=[1, 2, 3, 4], batches=[1, 2])
    assert spec.reader("requests_per_batch").read(readings) == 2.0
    assert spec.reader("waits.tiny").read(readings) == 1.0  # by the part before the dot
    with pytest.raises(run.BenchError, match="missing"):
        spec.config("no-such-config")


def test_a_mix_runs_on_a_config_at_a_given_rate(spec, tmp_path):
    """What the knee sweep does: a cell of its own, the rate set per run;
    with each request's times written out for a study of window lengths."""
    cell = {"name": "vgg16-224.poisson3_b8", "config": "vgg16-224", "traffic": "poisson3_b8",
            "chips": 1}
    line, e2e = run.execute(spec, cell, SEED, 1.0, False, smoke.CPU_DEVICE, smoke.peak(),
                            mix={"rate_hz": 50.0}, requests_out=str(tmp_path / "r.npz"))
    assert line["correct"] is True and line["attempted"] == 50
    assert e2e["backlog_at_close"] <= 8 and e2e["p95_latency_ms"] > 0
    z = np.load(tmp_path / "r.npz")
    assert len(z["due"]) == 50 and 0 <= z["due"].min() and z["due"].max() < 1.0
    assert np.percentile(z["done"] - z["due"], 95) * 1e3 == pytest.approx(e2e["p95_latency_ms"])


@pytest.mark.parametrize("config,want", [
    ("vgg16-224", {"img_res": 64, "in_channels": 3, "num_classes": 10, "width_mult": 0.125,
                   "blocks": [[2, 64], [2, 128], [3, 256], [3, 512], [3, 512]],
                   "fc_dims": [4096, 4096]}),
    ("vit-l16-224", {"img_res": 64, "in_channels": 3, "num_classes": 10, "patch": 8,
                     "n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128}),
])
def test_smoke_model_comes_from_the_registry(config, want):
    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    assert smoke.smoke_model(conf) == want


def test_a_new_configuration_file_comes_out_at_its_smoke_size(tmp_path):
    """A configuration of another registered architecture needs no edit of
    the smoke copy: its file alone is enough."""
    src = tmp_path / "src"
    shutil.copytree(BENCH, src / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(smoke.ROOT / "BENCHMARK.json", src / "BENCHMARK.json")
    (src / "bench" / "configs" / "swin-b-384.json").write_text(json.dumps(
        {"arch": "swin-b", "model": {
            "img_res": 384, "in_channels": 3, "num_classes": 1000, "patch": 4, "window": 12,
            "depths": [2, 2, 18, 2], "dims": [128, 256, 512, 1024], "n_heads": [4, 8, 16, 32],
            "mlp_ratio": 4}}))
    root, bench = smoke.make_root(tmp_path / "smoke", src / "bench")
    assert sorted(p.name for p in (bench / "configs").iterdir()) == [
        "swin-b-384.json", "vgg16-224.json", "vit-l16-224.json"]
    conf = run.Spec(root, bench).config("swin-b-384")
    assert conf["smoke"] is True
    assert conf["model"] == {
        "img_res": 64, "in_channels": 3, "num_classes": 10, "patch": 4, "window": 4,
        "depths": [2, 2], "dims": [32, 64], "n_heads": [2, 4], "mlp_ratio": 4}


def test_cell_file_overrides_its_mix(tmp_path):
    root, bench = smoke.make_root(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "vgg16-b8-open", "config": "vgg16-224",
                             "traffic": "poisson3_b8", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    (bench / "cells").mkdir()
    (bench / "cells" / "vgg16-b8-open.json").write_text(json.dumps({"rate_hz": 123.0}))
    mix = run.Spec(root, bench).mix(run.Spec(root, bench).cell("vgg16-b8-open"))
    assert mix["loop"] == "open" and mix["rate_hz"] == 123.0 and mix["max_batch"] == 8


# -- traffic ---------------------------------------------------------------------

def test_streams_are_fixed_by_the_seed():
    mix = {"loop": "open", "rate_hz": 1000.0, "pool": 16,
           "classes": [{"name": "a", "deadline_s": 0.1, "share": 0.2},
                       {"name": "b", "deadline_s": 1.0, "share": 0.8}]}
    a, b = arrivals.Stream(mix, SEED, 20.0, 0), arrivals.Stream(mix, SEED, 20.0, 0)
    c = arrivals.Stream(mix, SEED + 1, 20.0, 0)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.image, b.image)
    assert np.array_equal(a.cls, b.cls)
    assert not np.array_equal(a.due[:100], c.due[:100])
    # another seed: the same requests in another order
    assert len(a.due) == len(c.due) == 20000
    assert np.allclose(np.sort(np.diff(a.due, prepend=0)), np.sort(np.diff(c.due, prepend=0)))
    assert np.mean(np.diff(a.due)) == pytest.approx(1e-3, rel=1e-3)
    assert np.mean(a.cls == 0) == np.mean(c.cls == 0) == pytest.approx(0.2)
    counts = np.bincount(a.image, minlength=16)
    assert counts.min() == counts.max()  # every pool image equally often


# -- operation counts ------------------------------------------------------------

def test_vgg16_counts_match_the_geometry():
    from repro.configs import get

    cfg = get("vgg16").cfg
    geom = cfg.geom()
    convs = sum(geom.layer_flops(i) for i, g in enumerate(geom.layers) if g.kind != "pool")
    counts = run.load_py(BENCH / "counts" / "vgg16.py")
    model = json.loads((BENCH / "configs" / "vgg16-224.json").read_text())["model"]
    assert counts.flops_per_image(model) == pytest.approx(convs + geom.head_flops, rel=1e-12)
    assert counts.flops_per_image(model) == pytest.approx(30.94e9, rel=1e-3)


@pytest.mark.parametrize("config", ["vgg16-224", "vit-l16-224"])
def test_parameter_counts_match_the_program(config):
    import jax

    from repro.configs import get

    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    arch = get(conf["arch"])
    shapes = jax.eval_shape(lambda k: arch.module.init(k, arch.cfg), jax.random.PRNGKey(0))
    counts = run.load_py(BENCH / "counts" / f"{conf['arch']}.py")
    assert counts.params(conf["model"]) == sum(a.size for a in jax.tree.leaves(shapes))


def test_vit_counts_match_xla_cost_analysis():
    import jax
    import jax.numpy as jnp

    from repro.models import vit
    from repro.models.vit import ViTConfig

    # one block: XLA's cost analysis counts the body of the blocks' scan once
    m = {"img_res": 64, "in_channels": 3, "num_classes": 1000, "patch": 8, "n_layers": 1,
         "d_model": 256, "n_heads": 4, "d_ff": 1024}
    cfg = ViTConfig(**m, remat=False)
    params = jax.eval_shape(lambda k: vit.init(k, cfg), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 64, 64, 3), jnp.float32)
    cost = jax.jit(lambda p, x: vit.apply(p, cfg, x)).lower(params, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    counts = run.load_py(BENCH / "counts" / "vit-l16.py")
    ours = sum(f for _, f, _ in counts.layers(m, 2))
    # XLA also counts LayerNorm, softmax, GELU and bias adds, which ours leave out
    assert 0.9 * cost["flops"] <= ours <= cost["flops"]


# -- metric arithmetic -------------------------------------------------------------

def _req(due, done, deadline=1.0):
    return run.Request(due=due, image=0, deadline_s=deadline, done=done)


def test_end_to_end_takes_the_tail_of_every_request():
    rng = np.random.default_rng(0)
    lat = rng.exponential(0.01, size=1000)
    reqs = [_req(i * 0.01, i * 0.01 + lat[i]) for i in range(1000)]
    e2e = run.end_to_end(reqs, (0.0, 10.0))
    assert e2e["p95_latency_ms"] == pytest.approx(np.percentile(lat, 95) * 1e3)
    done_in = sum(1 for q in reqs if q.done <= 10.0)
    assert e2e["images_per_s"] == pytest.approx(done_in / 10.0)
    reqs.append(run.Request(due=9.9, image=0, deadline_s=1.0))  # never answered
    assert run.end_to_end(reqs, (0.0, 10.0))["deadline_met"] == pytest.approx(1000 / 1001)


def _readings(spec, cell, batches, trace=None):
    conf = spec.config(spec.cell(cell)["config"])
    model = json.loads((BENCH / "configs" / f"{spec.cell(cell)['config']}.json").read_text())
    return run.Readings(model=model["model"], counts=spec.counts(conf["arch"]),
                        peak=smoke.peak(), act_bytes=4, w_bytes=4, window=(0.0, 10.0),
                        untraced=(0.0, 8.0), width=8, model_module="jit_model",
                        batches=batches, requests=[], trace=trace)


def test_span_metrics(spec):
    batches = [{"step": (t, t + 0.010), "fn": (t + 0.002, t + 0.006), "width": 8, "n": n}
               for t, n in ((0.0, 8), (1.0, 4), (9.0, 8))]  # the last one is traced
    r = _readings(spec, "vgg16-b1", batches)
    assert spec.reader("engine_host_ms.latency").read(r) == pytest.approx(6.0)
    flops = 12 * r.flops_per_image()
    assert spec.reader("mfu.latency").read(r) == pytest.approx(
        100 * flops / 0.008 / smoke.peak()["bf16_flops"])


def test_roofline_share_is_100_when_ops_take_the_least_time(spec):
    r = _readings(spec, "vgg16-b8-saturate", [])
    least = r.min_time_s(8)
    r.trace = {"runs": {"jit_model": 10}, "op_s": {
        "jit_model:mxu": 10 * least - 0.004, "jit_model:move": 0.004, "jit_add:other": 1.0}}
    assert spec.reader("model_roofline.throughput").read(r) == pytest.approx(100.0)
    assert spec.reader("layout_ms.latency").read(r) == pytest.approx(0.4)
    r.trace["op_s"]["jit_model:other"] = 10 * least
    assert spec.reader("model_roofline.throughput").read(r) == pytest.approx(50.0)
    r.trace = None
    assert spec.reader("model_roofline.throughput").read(r) is None  # nothing to read


def test_scope_metrics(spec):
    r = _readings(spec, "vgg16-b1", [])
    r.trace = {"runs": {"jit_model": 4, "jit_concatenate": 1}, "op_s": {}, "scope_s": {
        "jit_model:head/fc1": 0.004, "jit_model:head/fc2": 0.0008, "jit_model:header": 1.0,
        "jit_model:layer03/exchange": 0.0004, "jit_model:layer12/exchange": 0.0004,
        "jit_model:layer03/e0": 1.0, "jit_model:attn": 0.012, "jit_model:(unscoped)": 0.002,
        "jit_concatenate:(unscoped)": 1.0}}
    assert spec.reader("head_ms.latency").read(r) == pytest.approx(1.2)  # with its children
    assert spec.reader("exchange_ms.latency").read(r) == pytest.approx(0.2)
    assert spec.reader("attn_ms.throughput").read(r) == pytest.approx(3.0)
    assert spec.reader("unscoped_ms.throughput").read(r) == pytest.approx(0.5)
    del r.trace["scope_s"]["jit_model:attn"]
    assert spec.reader("attn_ms.throughput").read(r) is None  # nothing to read
    r.trace = None
    assert spec.reader("head_ms.latency").read(r) is None


# -- trace reduction -----------------------------------------------------------------

HLO = """HloModule jit_model, entry_computation_layout={()}

%fused_computation.1 (param_0: bf16[8,16]) -> bf16[8,16] {
  %param_0 = bf16[8,16]{1,0} parameter(0)
  ROOT %convolution.3 = bf16[8,16]{1,0} convolution(bf16[8,16]{1,0} %param_0, bf16[8,16]{1,0} %param_0), dim_labels=b0f_0io->b0f, metadata={op_name="jit(model)/while/body/closed_call/checkpoint/attn/bhnm,bmhd->bnhd/dot_general" stack_frame_id=3}
}

%fused_computation.2 (param_0.1: f32[8,16]) -> bf16[8,16] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %slice.1 = f32[8,16]{1,0} slice(f32[8,16]{1,0} %param_0.1), slice={[0:8], [0:16]}
  ROOT %convert.1 = bf16[8,16]{1,0} convert(f32[8,16]{1,0} %slice.1), metadata={op_name="jit(model)/head/convert_element_type"}
}

%fused_computation.3 (param_0.2: bf16[8,16]) -> bf16[8,16] {
  %param_0.2 = bf16[8,16]{1,0} parameter(0)
  ROOT %maximum.1 = bf16[8,16]{1,0} maximum(bf16[8,16]{1,0} %param_0.2, bf16[8,16]{1,0} %param_0.2)
}

ENTRY %main.9 (batch.1: f32[8,16]) -> bf16[8,16] {
  %batch.1 = f32[8,16]{1,0} parameter(0)
  %fusion.2 = bf16[8,16]{1,0} fusion(f32[8,16]{1,0} %batch.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(model)/patch_embed/convert_element_type"}
  %fusion.1 = bf16[8,16]{1,0} fusion(bf16[8,16]{1,0} %fusion.2), kind=kOutput, calls=%fused_computation.1
  %copy-start = (f32[8,16]{1,0}, f32[8,16]{1,0}, u32[]) copy-start(f32[8,16]{1,0} %batch.1)
  %copy-done = f32[8,16]{1,0} copy-done((f32[8,16]{1,0}, f32[8,16]{1,0}, u32[]) %copy-start)
  ROOT %fusion.3 = bf16[8,16]{1,0} fusion(bf16[8,16]{1,0} %fusion.1), kind=kLoop, calls=%fused_computation.3
}
"""


def test_classify():
    c = xplane.classify(HLO)
    assert c["fusion.1"] == "mxu"
    assert c["fusion.2"] == "move"
    assert c["fusion.3"] == "other"
    assert c["copy-start"] == c["copy-done"] == "move"


@pytest.mark.parametrize("op_name,want", [
    ("jit(model)/while/body/closed_call/checkpoint/attn/reduce_sum", "attn"),  # frames
    ("jit(model)/remat/pjit/while/cond/mlp/add", "mlp"),
    ("jit(model)/while/body/closed_call/checkpoint/attn/bhnm,bmhd->bnhd/transpose", "attn"),
    ("jit(model)/head/fc1/jit(relu)/max", "head/fc1"),  # an inner jitted call
    ("jit(model)/layer00/e1/jit(relu)", "layer00/e1"),
    ("jit(model)/mlp/jit(_var)/square", "mlp"),
    ("jit(model)/layer03/exchange/concatenate", "layer03/exchange"),
    ("checkpoint/attn/reduce_sum", "attn"),  # inside a reduction's computation
    ("jit(model)/while/body/dynamic_slice", "(unscoped)"),
    ("jit(model)/jit(relu)", "(unscoped)"),
    ("reduce_sum", "(unscoped)"),  # a bare primitive
    ("params[\\'cls\\']", "(unscoped)"),  # a parameter's path
    (None, "(unscoped)"),
])
def test_scope_of_an_op_name(op_name, want):
    assert xplane.scope(op_name) == want


def test_scopes_of_the_instructions():
    sc = xplane.scopes(HLO)
    assert sc["convolution.3"] == "attn"
    assert sc["fusion.1"] == "attn"  # no op_name of its own: its root's
    assert sc["fusion.2"] == "patch_embed"  # its own, not its root's
    assert sc["convert.1"] == "head"
    assert sc["fusion.3"] == sc["maximum.1"] == "(unscoped)"  # no op_name at all
    assert sc["copy-start"] == "(unscoped)"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _profile():
    """Window 0-1000 ns; two model runs (100-300, 500-700, the second with a
    gap at 600-650); an eager op at 800-850; host spans around them."""
    ops = [_ev("%fusion.2 = bf16[8,16] fusion(...)", 100, 50),
           _ev("%fusion.1 = bf16[8,16] fusion(...), kind=kOutput", 150, 100),
           _ev("%fusion.3 = bf16[8,16] fusion(...)", 250, 50),
           _ev("%fusion.1 = bf16[8,16] fusion(...), kind=kOutput", 500, 100),
           _ev("%fusion.3 = bf16[8,16] fusion(...)", 650, 50),
           _ev("%copy.1 = f32[8,16] copy(...)", 800, 50)]
    mods = [_ev("jit_model(123)", 100, 200), _ev("jit_model(123)", 500, 200),
            _ev("jit_concatenate(9)", 800, 50)]
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=mods),
                                             NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 1000),
        _ev("engine.step", 50, 330), _ev("fn", 90, 220),
        _ev("wait", 380, 100),
        _ev("engine.step", 490, 400), _ev("fn", 495, 215)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), device, host])


def test_reduce_busy_classes_and_gaps():
    t = xplane.reduce(_profile(), {"jit_model": HLO})
    assert t["window_s"] == pytest.approx(1000e-9)
    assert t["busy_s"] == pytest.approx(400e-9)  # 100-300, 500-600, 650-700, 800-850
    assert t["runs"] == {"jit_model": 2, "jit_concatenate": 1}
    assert t["op_s"]["jit_model:mxu"] == pytest.approx(200e-9)
    assert t["op_s"]["jit_model:move"] == pytest.approx(50e-9)
    assert t["op_s"]["jit_model:other"] == pytest.approx(100e-9)
    gaps = dict(t["idle_gaps"])  # each gap labelled by the span at its midpoint
    assert gaps["engine.step"] == pytest.approx(200e-9)  # 0-100 and 700-800
    assert gaps["wait"] == pytest.approx(200e-9)  # 300-500
    assert gaps["fn"] == pytest.approx(50e-9)  # 600-650
    assert gaps["harness"] == pytest.approx(150e-9)  # 850-1000
    assert t["device_ops"][0] == ["jit_model/fusion.1", pytest.approx(200e-9)]
    assert t["scope_s"] == {"jit_model:attn": pytest.approx(200e-9),
                            "jit_model:patch_embed": pytest.approx(50e-9),
                            "jit_model:(unscoped)": pytest.approx(100e-9),
                            "jit_concatenate:(unscoped)": pytest.approx(50e-9)}
    _assert_scopes_sum_to_classes(t)


def _assert_scopes_sum_to_classes(t):
    for mod in {k.partition(":")[0] for k in t["op_s"]}:
        by_class = sum(v for k, v in t["op_s"].items() if k.partition(":")[0] == mod)
        by_scope = sum(v for k, v in t["scope_s"].items() if k.partition(":")[0] == mod)
        assert abs(by_scope - by_class) <= 1e-9
    assert {k.partition(":")[0] for k in t["scope_s"]} == {k.partition(":")[0] for k in t["op_s"]}


def test_reduce_without_device_plane_reads_nothing():
    p = _profile()
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    assert xplane.reduce(p) is None


RECORDED = BENCH / "tests" / "data"


def _recorded(name):
    from jax.profiler import ProfileData

    hlo = gzip.decompress((RECORDED / f"{name}.hlo.txt.gz").read_bytes()).decode()
    profile = ProfileData.from_serialized_xspace(
        gzip.decompress((RECORDED / f"{name}.xplane.pb.gz").read_bytes()))
    return xplane.reduce(profile, {"jit_model": hlo})


def test_recorded_chip_trace(spec):
    """A trace recorded on a TPU v5 lite (0.1 s of ``vgg16-b8-saturate``:
    8 model runs through the engine), with the model program's HLO text."""
    t = _recorded("vgg16-b8")
    assert 0 < t["busy_s"] <= t["window_s"]
    assert t["runs"]["jit_model"] == 8
    # per run: about 2.4 ms of conv and dot fusions, 0.2 ms moving data
    assert t["op_s"]["jit_model:mxu"] / 8 == pytest.approx(2.39e-3, rel=0.01)
    assert t["op_s"]["jit_model:move"] / 8 == pytest.approx(0.218e-3, rel=0.01)
    r = _readings(spec, "vgg16-b8-saturate", [], trace=t)
    assert 40 < spec.reader("model_roofline.throughput").read(r) < 50
    labels = {n for n, _ in t["idle_gaps"]}
    assert labels == {"fn", "engine.step", "harness"}
    assert dict(t["idle_gaps"])["engine.step"] > 0.5 * t["window_s"]  # the engine's host time
    # recorded before the program had named scopes: every op is unscoped
    assert {k for k in t["scope_s"] if k.startswith("jit_model:")} == {"jit_model:(unscoped)"}
    _assert_scopes_sum_to_classes(t)


@pytest.mark.parametrize("name,cell,runs,tops,want", [
    ("vit-l16-b8", "vit-l16-b8-saturate", 5, {"attn", "mlp", "patch_embed", "head", "(unscoped)"},
     {"attn_ms.throughput": (2.789, 2.792), "unscoped_ms.throughput": (2.905, 2.906)}),
    ("vgg16-b1", "vgg16-b1", 15, {"head", "merge", "(unscoped)"} | {f"layer{i:02d}" for i in range(18)},
     {"head_ms.latency": (0.5542, 0.5544), "exchange_ms.latency": (0.01838, 0.01839)}),
])
def test_recorded_chip_trace_by_scope(spec, name, cell, runs, tops, want):
    """Traces recorded on a TPU v5 lite (0.06 s of ``vit-l16-b8-saturate``,
    0.04 s of ``vgg16-b1``), with the model program's HLO text: device time
    by the program's scopes."""
    t = _recorded(name)
    assert t["runs"]["jit_model"] == runs
    found = {k.partition(":")[2].split("/")[0] for k in t["scope_s"] if k.startswith("jit_model:")}
    assert found == tops
    _assert_scopes_sum_to_classes(t)
    r = _readings(spec, cell, [], trace=t)
    for metric, (lo, hi) in want.items():  # the range of the traced chip runs (PERF.md)
        assert lo < spec.reader(metric).read(r) < hi
