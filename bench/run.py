"""One benchmark run of one cell, on the chip it is started on.

    python3 bench/run.py --workload vgg16-b1 --seed 7 --seconds 20 --trace 0

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; each is found by its name, as
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``, and a
cell's own numbers (a fixed rate) as ``bench/cells/<cell>.json``.  A
configuration names its architecture, whose plain reference is
``bench/reference/<arch>.py`` and whose operation counts are
``bench/counts/<arch>.py``; a per-layer metric ``<name>`` is read by
``bench/metrics/<name>.py`` or, failing that, by the file named after the
part of ``<name>`` before its first dot.

The run builds the model with the program's ``build_model``, feeds it
weights drawn from the seed by the reference module in one jitted call, makes
a pool of images on the device, warms up the cell's shapes through the
program's ``BatchingEngine``, drives that engine with the cell's traffic for
``--seconds``, drains it, and compares every served answer with the plain
reference.  Latency runs from when a request was due to when ``step()``
returned it.  ``--trace 1`` profiles the last seconds of the window and
reports the per-layer metrics instead of the end-to-end ones.

It exits non-zero, printing no result, unless JAX's first device is a TPU
whose ``device_kind`` is in ``bench/peaks.json`` and there are as many chips
as the cell asks for.  The last line of standard output is the result; the
numbers compared, each with its limit, are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
# the TPU library otherwise writes its logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import arrivals  # noqa: E402
import xplane  # noqa: E402

# the requests a closed-loop run draws classes and images for; it wraps round
CLOSED_CAP = 1 << 20
# traced part of the window, at its end
TRACE_S = 2.0
# how long after the window closes the run waits for the requests due in it
DRAIN_S = 60.0
# reference forward passes run this many pool images at a time
REF_BLOCK = 8
# element types, as HLO and NumPy name them, at least as precise as a
# configuration's stated operand precision
AT_LEAST = {
    "float32": {"f32", "f64", "float32", "float64"},
    "bfloat16": {"bf16", "f16", "f32", "f64", "bfloat16", "float16", "float32", "float64"},
}


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class BenchError(Exception):
    """A run that cannot be made: no result is printed."""


def load_py(path: Path):
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


class Spec:
    """``BENCHMARK.json`` and the files it names, found by name under ``bench``."""

    def __init__(self, root: Path = ROOT, bench: Path | None = None):
        self.root = Path(root)
        self.bench = Path(bench) if bench else HERE
        self.doc = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return load_json(self.bench / "configs" / f"{name}.json")

    def mix(self, cell: dict) -> dict:
        mix = load_json(self.bench / "traffic" / f"{cell['traffic']}.json")
        own = self.bench / "cells" / f"{cell['name']}.json"
        if own.is_file():
            mix.update(load_json(own))
        return mix

    def counts(self, arch: str):
        return load_py(self.bench / "counts" / f"{arch}.py")

    def reference(self, arch: str):
        return load_py(self.bench / "reference" / f"{arch}.py")

    def peaks(self) -> dict:
        return load_json(self.bench / "peaks.json")

    def metrics(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.doc[kind] if cell in m.get("workloads", [cell])]

    def reader(self, name: str):
        d = self.bench / "metrics"
        path = d / f"{name}.py"
        return load_py(path if path.is_file() else d / f"{name.split('.')[0]}.py")


def device_info(peaks: dict, chips: int) -> dict:
    """Platform, kind and count of JAX's devices; refuses anything but enough
    TPU chips of a kind in the peaks table."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"JAX's first device is {d.platform!r}, not a TPU")
    if d.device_kind not in peaks:
        raise BenchError(f"device kind {d.device_kind!r} is not in bench/peaks.json")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


class Collections:
    """Python's garbage collections: when each ran, its generation and how
    long it held the interpreter."""

    def __init__(self, clock):
        self.clock, self.runs, self._t0 = clock, [], 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = self.clock()
        else:
            self.runs.append((self._t0, info["generation"], self.clock() - self._t0))

    def between(self, t0: float, t1: float) -> list[tuple[float, int, float]]:
        return [c for c in self.runs if t0 <= c[0] <= t1]


class CompileEvents:
    """Times at which JAX compiled a program or loaded one from the
    persistent cache, and the cache's hits and misses."""

    def __init__(self, clock):
        import jax

        self.clock, self.times, self.hits, self.misses = clock, [], 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(self.clock())

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


@dataclass
class Request:
    due: float
    image: int
    deadline_s: float = 0.0
    done: float | None = None
    result: object = None


@dataclass
class Readings:
    """What a per-layer metric reader reads: the window's batches (host
    spans), its requests, the operation counts and, in a traced run, the
    reduced device trace."""

    model: dict
    counts: object
    peak: dict
    act_bytes: int
    w_bytes: float
    window: tuple[float, float]
    untraced: tuple[float, float]
    width: int
    model_module: str | None = None
    batches: list[dict] = field(default_factory=list)
    requests: list[Request] = field(default_factory=list)
    trace: dict | None = None

    def batches_in(self, span) -> list[dict]:
        return [b for b in self.batches if span[0] <= b["step"][0] and b["step"][1] <= span[1]]

    def flops_per_image(self) -> float:
        return self.counts.flops_per_image(self.model)

    def min_time_s(self, width: int) -> float:
        """Least time the chip needs for one model call of ``width`` images:
        the larger of the matrix layers' operations over the bf16 peak and,
        over HBM bandwidth, the bytes no schedule can avoid (every weight
        read once, the images read and the logits written once)."""
        flops = width * self.flops_per_image()
        m = self.model
        nbytes = (self.counts.params(m) * self.w_bytes
                  + width * m["img_res"] ** 2 * m["in_channels"] * self.act_bytes
                  + width * m["num_classes"] * 4)
        return max(flops / self.peak["bf16_flops"], nbytes / self.peak["hbm_bytes_per_s"])

    def scope_ms(self, scope: str) -> float | None:
        """Device time per model program run, in ms, of the ops in the
        program scopes that the regular expression ``scope`` matches whole,
        and in their children (``head`` takes ``head/fc1``), over the runs
        wholly inside the traced window.  ``None`` where no such op ran."""
        t, mod = self.trace, self.model_module
        if not t or not mod or not t["runs"].get(mod):
            return None
        want = re.compile(rf"(?:{scope})(?:/.*)?")
        found = [v for k, v in t["scope_s"].items()
                 if k.partition(":")[0] == mod and want.fullmatch(k.partition(":")[2])]
        return sum(found) / t["runs"][mod] * 1e3 if found else None


class Run:
    """One cell's run: set-up, measured window, drain, reference check.

    ``swap``, given the program's jitted model function (called as
    ``f(weights, batch)``), returns the function to serve in its place: tests
    and the control use it to put a broken or lower-precision function in
    the timed path.  ``mix`` overrides entries of the cell's traffic mix (the
    knee sweep's rates); ``workload`` is a cell's name or, for the sweep, a
    cell of its own."""

    def __init__(self, spec: Spec, workload: str | dict, seed: int, *, swap=None,
                 mix: dict | None = None, clock=time.monotonic):
        self.spec, self.seed, self.clock = spec, int(seed), clock
        self.cell = workload if isinstance(workload, dict) else spec.cell(workload)
        self.conf = spec.config(self.cell["config"])
        self.mix = {**spec.mix(self.cell), **(mix or {})}
        self.arch = self.conf["arch"]
        self.model = self.conf["model"]
        self.operands = self.conf["precision"]["operands"]
        self.swap = swap
        self.compiles = CompileEvents(clock)

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.launch.serve import build_model
        from repro.runtime.serve import BatchingEngine, ServeConfig

        cfg, params, fn = build_model(self.arch, seed=self.seed,
                                      smoke=bool(self.conf.get("smoke", False)))
        for k, v in self.model.items():
            got = getattr(cfg, k)
            if json.loads(json.dumps(got)) != v:
                raise BenchError(f"program's {self.arch} has {k}={got!r}, "
                                 f"configuration {self.cell['config']} says {v!r}")
        if not isinstance(fn, functools.partial):
            raise BenchError("build_model no longer returns partial(jitted model, params)")
        self.ref = self.spec.reference(self.arch)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed % 2**32), self.seed >> 32)
        self.k_w, k_x = jax.random.split(key)
        # the reference's weights, in the types the program keeps its own in
        kinds = jax.tree.map(lambda a: a.dtype, params)
        shapes = jax.eval_shape(functools.partial(self.ref.weights, m=self.model), self.k_w)
        if jax.tree.map(lambda a: a.shape, shapes) != jax.tree.map(lambda a: a.shape, params):
            raise BenchError("reference weights differ in layout from the program's")
        narrow = {str(t) for t in jax.tree.leaves(kinds)} - AT_LEAST[self.operands]
        if narrow:
            raise BenchError(f"the program keeps weights in {sorted(narrow)}, below the "
                             f"configuration's {self.operands} operands")
        del params
        self.weights = jax.jit(lambda k: jax.tree.map(
            lambda a, t: a.astype(t), self.ref.weights(k, self.model), kinds))(self.k_w)
        self._program = self.swap(fn.func) if self.swap else fn.func
        del fn
        self.fn = functools.partial(self._program, self.weights)

        res, ch = self.model["img_res"], self.model["in_channels"]
        image = jax.jit(lambda k, i: jax.random.normal(jax.random.fold_in(k, i), (res, res, ch),
                                                       jnp.dtype(self.conf["dtype"])))
        self.pool = [image(k_x, i) for i in range(int(self.mix["pool"]))]
        self.act_bytes = self.pool[0].dtype.itemsize
        leaves = jax.tree.leaves(self.weights)
        self.w_bytes = sum(a.nbytes for a in leaves) / sum(a.size for a in leaves)

        self.batches: list[dict] = []
        self._width: list[int] = []
        self.engine = BatchingEngine(self._timed, ServeConfig(
            max_batch=int(self.mix["max_batch"]), max_delay_s=float(self.mix["max_delay_s"])),
            clock=self.clock, observer=lambda width, _dt: self._width.append(width))
        # every shape of the window: the engine's stack, the model at the
        # executed width and the slice of each row, twice
        for _ in range(2):
            for i in range(self.engine.cfg.max_batch):
                self.engine.submit(self.pool[i % len(self.pool)], 1.0)
            jax.block_until_ready([r.result for r in self.engine.step()])
        self.batches.clear()
        self._width.clear()

    def _timed(self, batch):
        import jax

        with jax.profiler.TraceAnnotation("fn"):
            t0 = self.clock()
            out = jax.block_until_ready(self.fn(batch))
            t1 = self.clock()
        self._fn_span = (t0, t1)
        return out

    # -- window -----------------------------------------------------------
    def window(self, seconds: float, trace: bool) -> None:
        import jax

        self.seconds = float(seconds)
        stream = arrivals.Stream(self.mix, self.seed, self.seconds, CLOSED_CAP)
        deadlines = [float(c["deadline_s"]) for c in stream.classes]
        eng, clock = self.engine, self.clock
        reqs: dict[int, Request] = {}
        self.requests: list[Request] = []
        lateness: list[float] = []
        k = 0

        def submit(due: float, now: float) -> None:
            nonlocal k
            j = k % len(stream.image)
            k += 1
            q = Request(due=due, image=int(stream.image[j]),
                        deadline_s=deadlines[int(stream.cls[j])])
            reqs[eng.submit(self.pool[q.image], q.deadline_s)] = q
            self.requests.append(q)
            lateness.append(now - due)

        def step(resubmit_until: float | None) -> None:
            with jax.profiler.TraceAnnotation("engine.step"):
                s0 = clock()
                done = eng.step()
                s1 = clock()
            self.batches.append({"step": (s0, s1), "fn": self._fn_span,
                                 "width": self._width[-1], "n": len(done)})
            for r in done:
                q = reqs.pop(r.rid)
                q.done, q.result = s1, r.result
                if resubmit_until is not None and s1 < resubmit_until:
                    submit(s1, clock())

        t0 = clock()
        end = t0 + self.seconds
        self.t_window = (t0, end)
        trace_at = end - min(TRACE_S, self.seconds / 2) if trace else None
        self.trace_dir = self.trace_span = None
        marker = None
        due = None if stream.due is None else t0 + stream.due
        i = 0
        if due is None:
            for _ in range(int(self.mix["clients"])):
                submit(t0, t0)
        while True:
            now = clock()
            if due is not None:
                while i < len(due) and due[i] <= now:
                    submit(float(due[i]), now)
                    i += 1
            if now >= end:
                break
            if trace_at is not None and marker is None and now >= trace_at:
                marker = self._start_trace()
                continue
            if eng.ready():
                step(end if due is None else None)
                continue
            nxt = end if due is None or i >= len(due) else min(end, float(due[i]))
            with jax.profiler.TraceAnnotation("wait"):
                # the engine forms a partial batch after max_delay_s: look again soon
                limit = min(nxt, now + eng.cfg.max_delay_s / 4)
                while clock() < limit:
                    pass
        self.backlog = len(eng.queue)
        if marker is not None:
            self._stop_trace(marker)
        self.attempted = len(self.requests)
        drain_end = clock() + DRAIN_S
        while eng.queue and clock() < drain_end:
            step(None)
        self.lateness = np.asarray(lateness)
        self.hlo = self._compiled_text()
        self.narrow = [(op, types) for text in self.hlo.values()
                       for op, types in xplane.operand_types(text)
                       if not set(types) <= AT_LEAST[self.operands]]

    def _compiled_text(self) -> dict[str, str]:
        """The model program's HLO text by module name, so that the trace's
        ops can be classed and its operands' types read (a cache hit: the
        warm-up compiled it)."""
        import jax

        x = self.pool[0]
        shape = jax.ShapeDtypeStruct((self.engine.cfg.max_batch, *x.shape), x.dtype)
        text = self._program.lower(self.weights, shape).compile().as_text()
        return {text.split(",", 1)[0].split()[-1]: text}

    def _start_trace(self):
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        marker = jax.profiler.TraceAnnotation("bench.window")
        marker.__enter__()
        self._trace_t0 = self.clock()
        return marker

    def _stop_trace(self, marker) -> None:
        import jax

        t1 = self.clock()
        marker.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.trace_span = (self._trace_t0, t1)

    # -- after the window -------------------------------------------------
    def readings(self) -> Readings:
        t0, end = self.t_window
        untraced = (t0, self.trace_span[0] if self.trace_span else end)
        counts = self.spec.counts(self.arch)
        trace = None
        if self.trace_dir:
            try:
                trace = xplane.reduce_dir(self.trace_dir, self.hlo)
            finally:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir = None
        return Readings(model=self.model, counts=counts,
                        peak=self.peak, act_bytes=self.act_bytes, w_bytes=self.w_bytes,
                        window=self.t_window, untraced=untraced,
                        width=self.engine.cfg.max_batch, model_module=next(iter(self.hlo), None),
                        batches=self.batches,
                        requests=self.requests, trace=trace)

    def end_to_end(self) -> dict:
        e2e = end_to_end(self.requests, self.t_window)
        e2e.update(setup_s=self.t_window[0] - self.t_setup0, backlog_at_close=self.backlog)
        return e2e

    def free_program(self) -> list:
        """Answers as host arrays, in request order; drops the program's
        state (engine, compiled function, device results)."""
        import jax

        got = jax.device_get([q.result for q in self.requests if q.done is not None])
        for q in self.requests:
            q.result = None
        del self.engine, self.fn, self.weights
        return got

    def check(self, got: list) -> dict:
        """Compares every served answer with the reference's answer for its
        pool image: the largest ``max|served - ref| / max|ref|`` over the
        requests served; the served program's convolutions and dots with an
        operand below the configuration's stated precision; and the requests
        never answered."""
        import jax
        import jax.numpy as jnp

        weights = jax.jit(functools.partial(self.ref.weights, m=self.model))(self.k_w)
        fwd = jax.jit(functools.partial(self.ref.forward, m=self.model, mode="highest"))
        want = np.concatenate([
            np.asarray(fwd(weights, jnp.stack(self.pool[i:i + REF_BLOCK])))
            for i in range(0, len(self.pool), REF_BLOCK)])
        served = [q for q in self.requests if q.done is not None]
        err = 0.0
        for q, y in zip(served, got):
            y = np.asarray(y, np.float64)
            ref = want[q.image].astype(np.float64)
            if y.shape != ref.shape or not np.isfinite(y).all():
                err = float("inf")
                break
            err = max(err, float(np.abs(y - ref).max() / np.abs(ref).max()))
        return {
            "logit_err": {"value": err, "limit": float(self.conf["limits"]["logit_err"])},
            "narrow_operands": {"value": len(self.narrow), "limit": 0},
            "failed": {"value": self.attempted - len(served), "limit": 0},
        }


def end_to_end(requests: list[Request], window: tuple[float, float]) -> dict:
    """Latency percentiles over every request due in the window (from due
    to returned, drained ones included), the share that met its deadline
    (one never returned misses), and images completed in the window per
    second of it."""
    t0, end = window
    served = [q for q in requests if q.done is not None]
    lat = np.array([q.done - q.due for q in served])

    def pct(p):
        return float(np.percentile(lat, p) * 1e3) if lat.size else None

    return {
        "p50_latency_ms": pct(50), "p95_latency_ms": pct(95), "p99_latency_ms": pct(99),
        "deadline_met": sum(q.done - q.due <= q.deadline_s for q in served) / max(1, len(requests)),
        "images_per_s": sum(1 for q in served if q.done <= end) / (end - t0),
    }


def result_line(run: Run, dev: dict, trace: bool, checks: dict, e2e: dict,
                readings: Readings | None) -> dict:
    metrics = {}
    if trace:
        for m in run.spec.metrics(run.cell["name"], "per_layer"):
            v = run.spec.reader(m["name"]).read(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in run.spec.metrics(run.cell["name"], "end_to_end"):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = dict(dev)
    if trace and readings.trace:
        device["busy_s"] = readings.trace["busy_s"]
        device["window_s"] = readings.trace["window_s"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": run.attempted,
            "failed": checks["failed"]["value"], "metrics": metrics, "device": device}
    if trace and readings.trace:
        line["breakdown"] = {"device_ops": readings.trace["device_ops"],
                             "idle_gaps": readings.trace["idle_gaps"]}
    line["checks"] = checks
    return line


def execute(spec: Spec, workload: str | dict, seed: int, seconds: float, trace: bool,
            dev: dict, peak: dict, swap=None, mix: dict | None = None,
            requests_out: str | None = None) -> tuple[dict, dict]:
    """Everything of a run after the look for a chip: the result line, and
    every end-to-end number the run took (the knee sweep reads them).
    ``requests_out`` names an ``.npz`` file for each request's due and
    return times, in seconds from the window's start (NaN: never returned)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # small programs too go to the cache, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = Run(spec, workload, seed, swap=swap, mix=mix)
    run.t_setup0, run.peak = T_START, peak
    run.setup()
    n_setup, hits, misses = len(run.compiles.times), run.compiles.hits, run.compiles.misses
    collections = Collections(run.clock)
    run.window(seconds, trace)
    e2e = run.end_to_end()
    if requests_out:
        np.savez_compressed(
            requests_out, seconds=seconds,
            due=np.array([q.due - run.t_window[0] for q in run.requests]),
            done=np.array([np.nan if q.done is None else q.done - run.t_window[0]
                           for q in run.requests]))
    readings = run.readings()
    dev = dict(dev)
    dev["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())
    got = run.free_program()
    checks = run.check(got)

    lat = run.lateness
    t0, end = run.t_window
    log(f"{run.cell['name']}: seed {seed}, {run.attempted} requests due in {seconds:g} s, "
        f"{len(got)} served, {len(run.batches)} batches")
    log(f"set-up {e2e['setup_s']:.3f} s: {n_setup} programs compiled or loaded "
        f"(persistent cache hits {hits}, misses {misses}); "
        f"compilations in the window: {run.compiles.between(t0, end)}; after it: "
        f"{len(run.compiles.times) - n_setup - run.compiles.between(t0, end)} "
        f"(misses {run.compiles.misses - misses})")
    log(f"latency ms p50 {e2e['p50_latency_ms']} p95 {e2e['p95_latency_ms']} "
        f"p99 {e2e['p99_latency_ms']}; images/s {e2e['images_per_s']:.3f}; "
        f"deadlines met {e2e['deadline_met']:.4f}")
    if lat.size:
        log(f"generator late by ms: mean {lat.mean() * 1e3:.4f} p99 "
            f"{np.percentile(lat, 99) * 1e3:.4f} max {lat.max() * 1e3:.4f}")
    gcs = collections.between(t0, end)
    gc.callbacks.remove(collections._on)
    log(f"garbage collections in the window: {len(gcs)}, "
        f"{sum(1 for c in gcs if c[1] == 2)} of the oldest generation, longest "
        f"{max((c[2] for c in gcs), default=0.0) * 1e3:.3f} ms")
    if run.batches:
        s0, s1 = max((b["step"] for b in run.batches), key=lambda s: s[1] - s[0])
        where = "traced part" if run.trace_span and s0 >= run.trace_span[0] else "untraced part"
        log(f"longest engine step {(s1 - s0) * 1e3:.3f} ms, {s0 - t0:.3f} s into the window "
            f"({where if s0 < end else 'drain'})")
    for op, types in run.narrow:
        log(f"{op} takes operands {types}, below {run.operands}")
    widths = [b["n"] for b in run.batches if b["step"][1] <= end]
    if widths:
        log(f"real requests per batch in the window: mean {np.mean(widths):.3f}; "
            f"queued at the close: {run.backlog}")
    if readings.trace and readings.trace["runs"].get(readings.model_module):
        tops = {k.partition(":")[2].split("/")[0] for k in readings.trace["scope_s"]
                if k.partition(":")[0] == readings.model_module}
        by_top = {sc: readings.scope_ms(re.escape(sc)) for sc in tops}
        log("device ms per model run by scope: " + ", ".join(
            f"{sc} {v:.4f}" for sc, v in sorted(by_top.items(), key=lambda x: -x[1])))
    line = result_line(run, dev, trace, checks, e2e, readings)
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return line, e2e


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--requests-out", help="write each request's due and return times here (.npz)")
    args = ap.parse_args(argv)
    try:
        spec = Spec()
        cell = spec.cell(args.workload)
        peaks = spec.peaks()
        sys.path.insert(0, str(spec.root / "src"))
        dev = device_info(peaks, int(cell["chips"]))
        line, _ = execute(spec, args.workload, args.seed, args.seconds, bool(args.trace),
                          dev, peaks[dev["kind"]], requests_out=args.requests_out)
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
