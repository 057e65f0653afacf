"""Serving-pipeline conformance harness: traffic models, admission, the
event-driven virtual-time loop (this subsystem's ``test_conformance.py``).

Everything runs in simulated time -- there is no ``time.sleep`` anywhere and
no wall-clock assertion; the :class:`~repro.runtime.serve.VirtualClock` and
the trace loop's virtual event clock are the only notions of time."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.reliability import (
    OffloadChannel,
    phi,
    probit,
    required_slack,
    service_reliability,
)
from repro.runtime.serve import (
    BatchingEngine,
    ServeConfig,
    ServedTrace,
    ServeLoopConfig,
    VirtualClock,
    choose_batch_size,
    serve_trace,
)
from repro.runtime.traffic import (
    DeadlineClass,
    DiurnalProcess,
    FlashCrowdProcess,
    PoissonProcess,
    Trace,
    make_trace,
)

LAT = np.array([0.012, 0.016, 0.020, 0.024, 0.028, 0.032, 0.036, 0.040])
CLASSES = (
    DeadlineClass("premium", 0.15, target=0.999, share=0.2),
    DeadlineClass("standard", 0.4, target=0.99, share=0.5),
    DeadlineClass("bulk", 2.0, target=0.9, share=0.3),
)
CH = OffloadChannel(rate_bps=100e6, sigma_s=2e-3)  # mu = 40 ms
CH0 = OffloadChannel(rate_bps=100e6, sigma_s=0.0)


def _assert_served_equal(a: ServedTrace, b: ServedTrace) -> None:
    assert np.array_equal(a.fin, b.fin, equal_nan=True)
    assert np.array_equal(a.shed, b.shed)
    assert np.array_equal(a.met, b.met)
    assert a.n_batches == b.n_batches
    assert np.array_equal(a.batch_size_counts, b.batch_size_counts)


# ---------------------------------------------------------------------------
# probit / required_slack: the reliability integral inverted for admission
# ---------------------------------------------------------------------------


def test_probit_inverts_phi():
    for p in (0.5, 0.9, 0.99, 0.999, 0.99999, 0.1, 0.025):
        assert phi(probit(p)) == pytest.approx(p, abs=1e-9)
    assert probit(0.5) == pytest.approx(0.0, abs=1e-9)
    for bad in (0.0, 1.0, -0.1, 1.1):
        with pytest.raises(ValueError):
            probit(bad)


def test_required_slack_inverts_service_reliability():
    """reliability(ch, t_inf, D) >= target  iff  D >= required_slack: the
    threshold sits exactly at the target's quantile."""
    t_inf = 0.02
    for target in (0.9, 0.99, 0.999):
        d = required_slack(CH, t_inf, target)
        assert service_reliability(CH, t_inf, d) == pytest.approx(target, abs=1e-9)
        assert service_reliability(CH, t_inf, d + 1e-6) > target
        assert service_reliability(CH, t_inf, d - 1e-6) < target
    # monotone in target; degenerate deterministic channel
    assert required_slack(CH, t_inf, 0.999) > required_slack(CH, t_inf, 0.9)
    assert required_slack(CH0, t_inf, 0.42) == CH0.mu_s + t_inf
    with pytest.raises(ValueError):
        required_slack(CH, t_inf, 1.0)


# ---------------------------------------------------------------------------
# VirtualClock + asynchronous batch formation (ready/poll)
# ---------------------------------------------------------------------------


def test_virtual_clock_semantics():
    clk = VirtualClock(start_s=5.0)
    assert clk() == 5.0 and clk.now() == 5.0
    assert clk.advance(1.5) == 6.5
    assert clk.advance_to(10.0) == 10.0
    with pytest.raises(ValueError):
        clk.advance(-0.1)
    with pytest.raises(ValueError):
        clk.advance_to(9.0)
    assert clk() == 10.0  # failed moves leave time untouched


def test_batch_formation_decoupled_from_execution():
    """ready()/poll(): a batch launches when full OR when the head request has
    waited max_delay_s -- a pure decision on (queue, clock), no sleeping."""
    clk = VirtualClock()
    eng = BatchingEngine(
        jax.jit(lambda b: b), ServeConfig(max_batch=3, max_delay_s=0.010), clock=clk
    )
    assert not eng.ready() and eng.poll() == []  # empty queue never launches
    eng.submit(jnp.zeros(()), deadline_s=1.0)
    assert not eng.ready()  # neither full nor timed out
    clk.advance(0.005)
    assert not eng.ready() and eng.poll() == []
    clk.advance(0.005)  # head has now waited exactly max_delay_s (0.005*2
    # is binary-exactly the 0.01 literal; 0.009+0.001 would not be)
    assert eng.ready()
    done = eng.poll()
    assert len(done) == 1 and not eng.queue
    # full batch launches immediately, with no waiting at all
    for _ in range(3):
        eng.submit(jnp.zeros(()), deadline_s=1.0)
    assert eng.ready()
    assert len(eng.poll()) == 3


# ---------------------------------------------------------------------------
# BatchingEngine edge cases
# ---------------------------------------------------------------------------


def test_edf_pop_with_duplicate_deadlines():
    """Duplicate deadlines must not break the heap pop: all duplicates drain,
    and a strictly earlier deadline still precedes every duplicate."""
    clk = VirtualClock()
    eng = BatchingEngine(jax.jit(lambda b: b), ServeConfig(max_batch=3), clock=clk)
    dup = [eng.submit(jnp.zeros(()), deadline_s=2.0) for _ in range(3)]
    tight = eng.submit(jnp.zeros(()), deadline_s=0.5)
    first = eng.step()
    assert first[0].rid == tight  # earliest deadline leads the first batch
    assert {r.rid for r in first[1:]} <= set(dup)
    rest = eng.step()
    assert {r.rid for r in first[1:]} | {r.rid for r in rest} == set(dup)


def test_stats_on_zero_and_one_completed():
    clk = VirtualClock()
    eng = BatchingEngine(jax.jit(lambda b: b), ServeConfig(max_batch=2), clock=clk)
    s0 = eng.stats()
    assert s0["completed"] == 0 and s0["deadline_met_frac"] == 0.0
    assert s0["p50_latency_s"] == 0.0 and s0["p99_latency_s"] == 0.0  # no NaNs
    eng.submit(jnp.zeros(()), deadline_s=1.0)
    clk.advance(0.25)
    eng.step()
    s1 = eng.stats()
    assert s1["completed"] == 1 and s1["deadline_met_frac"] == 1.0
    # a single sample is every percentile of itself
    assert s1["p50_latency_s"] == pytest.approx(0.25)
    assert s1["p99_latency_s"] == pytest.approx(0.25)


def test_run_until_drained_respects_max_batches():
    eng = BatchingEngine(jax.jit(lambda b: b), ServeConfig(max_batch=4))
    for i in range(10):
        eng.submit(jnp.ones(()) * i, deadline_s=5.0)
    stats = eng.run_until_drained(max_batches=2)
    assert stats["completed"] == 8  # two full batches executed...
    assert len(eng.queue) == 2  # ...and the residual queue is intact
    eng.run_until_drained()
    assert eng.stats()["completed"] == 10 and not eng.queue


def test_pad_to_max_reports_executed_width_variants():
    """pad_to_max=True reports the padded (executed) width; False the true
    request count -- the replan calibration depends on the distinction."""
    for pad, want in ((True, [4, 4, 4]), (False, [4, 4, 2])):
        seen = []
        eng = BatchingEngine(
            jax.jit(lambda b: b),
            ServeConfig(max_batch=4, pad_to_max=pad),
            observer=lambda n, dt: seen.append(n),
        )
        for i in range(10):
            eng.submit(jnp.ones(()) * i, deadline_s=5.0)
        eng.run_until_drained()
        assert seen == want


# ---------------------------------------------------------------------------
# BatchingEngine spans (repro.runtime.tracing)
# ---------------------------------------------------------------------------


def _ticking_clock():
    """A clock that moves one unit at every reading, so every span has a
    length and nesting shows in the times."""
    t = iter(range(10**9))
    return lambda: float(next(t))


def _engine(spans=None, clock=None):
    eng = BatchingEngine(jax.jit(lambda b: b * 2.0), ServeConfig(max_batch=4),
                         clock=clock or VirtualClock(), spans=spans)
    for i in range(10):
        eng.submit(jnp.ones((3,)) * i, deadline_s=5.0 - 0.1 * i)
    return eng


def test_engine_spans_nest_per_batch():
    """One ``serve.step`` per batch, numbered from 1, holding the request ids
    it served and its real and executed widths, with ``serve.stack``,
    ``serve.call`` and ``serve.split`` of the same step inside it, in turn."""
    from repro.runtime.tracing import SpanLog

    clock = _ticking_clock()
    log = SpanLog(clock)
    eng = _engine(log, clock)
    batches = [[r.rid for r in eng.step()] for _ in range(3)]
    assert eng.step() == [] and not eng.queue
    steps = [s for s in log.spans if s.name == "serve.step"]
    assert [s.step for s in steps] == [1, 2, 3]
    assert [s.info["rids"] for s in steps] == batches
    assert [(s.info["width"], s.info["executed"]) for s in steps] == [(4, 4), (4, 4), (2, 4)]
    for parent in steps:
        kids = [s for s in log.spans if s.step == parent.step and s is not parent]
        assert [k.name for k in kids] == ["serve.stack", "serve.call", "serve.split"]
        assert parent.t0 < kids[0].t0 and kids[-1].t1 < parent.t1
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
    assert len(log.spans) == 12


def test_engine_span_log_leaves_results_unchanged(monkeypatch):
    """Batches and results with a span log equal those without; an engine
    without one opens no profiler annotation."""
    from repro.runtime.tracing import SpanLog

    clock = VirtualClock()
    plain, traced = _engine(), _engine(SpanLog(clock), clock)
    opened = []
    real = jax.profiler.TraceAnnotation
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **kw: opened.append(name) or real(name, **kw))
    a = plain.run_until_drained()
    assert opened == []
    b = traced.run_until_drained()
    assert opened[:4] == ["serve.step", "serve.stack", "serve.call", "serve.split"]
    assert a == b and len(traced.spans.spans) == 12
    assert [r.rid for r in plain.completed] == [r.rid for r in traced.completed]
    for x, y in zip(plain.completed, traced.completed):
        np.testing.assert_array_equal(np.asarray(x.result), np.asarray(y.result))


# Payload rows by kind: a row builder from request index, and its model.  The
# pytree rows carry a Python float, so their host variant mixes leaf types.
_ROWS = {
    "scalar": (lambda i: np.float32(i) / np.float32(3),
               lambda b: b * 2.0 + 1.0),
    "array": (lambda i: np.arange(6, dtype=np.float32).reshape(2, 3) * np.float32(i / 7),
              lambda b: jnp.tanh(b) @ jnp.full((3, 4), 0.5)),
    "pytree": (lambda i: {"x": np.arange(3, dtype=np.float32) + i, "s": float(i) / 4},
               lambda b: {"y": b["x"] * b["s"][:, None], "n": jnp.sum(b["x"], axis=1)}),
}


@pytest.mark.parametrize("rows", sorted(_ROWS))
@pytest.mark.parametrize("pad", [True, False], ids=["pad", "nopad"])
@pytest.mark.parametrize("where", ["device", "numpy"])
def test_engine_results_are_host_rows_of_each_batch(where, pad, rows):
    """Every request's result is, bit for bit, row ``i`` of its batch's
    output computed from an eager ``jnp.stack`` of the padded payloads (the
    per-request ``out[i]`` the engine used to return); every leaf is a host
    array; padded rows reach no request."""
    make, model = _ROWS[rows]
    fn = jax.jit(model)
    clk = VirtualClock()
    eng = BatchingEngine(fn, ServeConfig(max_batch=4, pad_to_max=pad), clock=clk)
    payloads = {}
    for i in range(10):
        row = make(i)
        if where == "device":
            row = jax.tree_util.tree_map(jnp.asarray, row)
        payloads[eng.submit(row, deadline_s=5.0 - 0.1 * i)] = row
    batches = [eng.step() for _ in range(3)]
    assert [len(b) for b in batches] == [4, 4, 2] and not eng.queue
    assert sorted(r.rid for r in eng.completed) == sorted(payloads)
    for batch in batches:
        padded = [payloads[r.rid] for r in batch]
        padded += [padded[-1]] * ((4 if pad else len(batch)) - len(batch))
        out = fn(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded))
        for i, r in enumerate(batch):
            want = jax.tree_util.tree_map(lambda x: x[i], out)
            assert jax.tree_util.tree_structure(r.result) == jax.tree_util.tree_structure(want)
            for got, ref in zip(jax.tree_util.tree_leaves(r.result),
                                jax.tree_util.tree_leaves(want)):
                assert isinstance(got, np.ndarray) and not isinstance(got, jax.Array)
                assert got.dtype == ref.dtype and got.shape == ref.shape
                np.testing.assert_array_equal(got, np.asarray(ref))


@pytest.mark.parametrize("pad,widths", [(True, (4, 2)), (False, (3, 3))],
                         ids=["pad", "nopad"])
def test_engine_second_batch_at_same_width_compiles_nothing(pad, widths):
    """Stacking, the model call and the split compile on the first batch of
    a width and never again: a later batch that runs at the same executed
    width compiles nothing."""
    compiles = []

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    eng = BatchingEngine(jax.jit(lambda b: jnp.tanh(b) * 3.0 + 1.0),
                         ServeConfig(max_batch=4, pad_to_max=pad))
    rows = jax.device_put(np.arange(40, dtype=np.float32).reshape(4, 5, 2))
    rows = [rows[i] for i in range(4)]
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        seen = []
        for n in widths:
            for row in rows[:n]:
                eng.submit(row, deadline_s=5.0)
            assert len(eng.step()) == n
            seen.append(len(compiles))
            compiles.clear()
        assert seen[0] > 0 and seen[1:] == [0], seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def test_build_model_records_setup_spans():
    from repro.launch.serve import build_model
    from repro.runtime.tracing import SpanLog

    log = SpanLog()
    build_model("vgg16", smoke=True, spans=log)
    assert [(s.name, s.step) for s in log.spans] == [("build.init", None), ("build.plan", None)]
    assert all(s.t0 <= s.t1 for s in log.spans)


# ---------------------------------------------------------------------------
# choose_batch_size properties (the PR-5 shed semantics, property-tested)
# ---------------------------------------------------------------------------

_lat_base = st.floats(min_value=1e-4, max_value=5e-2)
_lat_slope = st.floats(min_value=1e-5, max_value=2e-2)
_deadline = st.floats(min_value=1e-3, max_value=1.0)
_target = st.floats(min_value=0.5, max_value=0.999999)
_sigma = st.floats(min_value=0.0, max_value=2e-2)
_rate = st.floats(min_value=2e6, max_value=1e9)


@settings(max_examples=60, deadline=None)
@given(a=_lat_base, c=_lat_slope, d1=_deadline, d2=_deadline, sig=_sigma, rate=_rate)
def test_choose_batch_size_monotone_in_deadline(a, c, d1, d2, sig, rate):
    ch = OffloadChannel(rate_bps=rate, sigma_s=sig)
    lat = lambda b: a + c * b
    lo, hi = min(d1, d2), max(d1, d2)
    assert choose_batch_size(lat, lo, ch, target=0.99, max_batch=16) <= choose_batch_size(
        lat, hi, ch, target=0.99, max_batch=16
    )


@settings(max_examples=60, deadline=None)
@given(a=_lat_base, c=_lat_slope, d=_deadline, t1=_target, t2=_target, rate=_rate)
def test_choose_batch_size_antitone_in_target(a, c, d, t1, t2, rate):
    ch = OffloadChannel(rate_bps=rate, sigma_s=5e-3)
    lat = lambda b: a + c * b
    lo, hi = min(t1, t2), max(t1, t2)
    assert choose_batch_size(lat, d, ch, target=lo, max_batch=16) >= choose_batch_size(
        lat, d, ch, target=hi, max_batch=16
    )


@settings(max_examples=60, deadline=None)
@given(
    a=_lat_base, c=_lat_slope, d=_deadline, t=_target, sig=_sigma, rate=_rate,
    mb=st.integers(min_value=1, max_value=24),
)
def test_choose_batch_size_bounds_and_shed_semantics(a, c, d, t, sig, rate, mb):
    """0 <= result <= max_batch, and 0 means even b=1 misses the target."""
    ch = OffloadChannel(rate_bps=rate, sigma_s=sig)
    lat = lambda b: a + c * b
    b = choose_batch_size(lat, d, ch, target=t, max_batch=mb)
    assert 0 <= b <= mb
    if b == 0:
        assert service_reliability(ch, lat(1), d) < t
    else:
        assert service_reliability(ch, lat(b), d) >= t


# ---------------------------------------------------------------------------
# Arrival generators: seeded determinism + rate semantics
# ---------------------------------------------------------------------------


def test_generators_seeded_determinism():
    """Same seed => bit-identical trace (fresh instances); different seed
    diverges.  Holds for every process and for make_trace's labels."""
    procs = [
        lambda seed: PoissonProcess(rate_hz=20.0, seed=seed),
        lambda seed: DiurnalProcess(base_rate_hz=15.0, period_s=100.0, seed=seed),
        lambda seed: FlashCrowdProcess(base_rate_hz=10.0, seed=seed),
    ]
    for make in procs:
        t1, t2 = make(5).times(50.0), make(5).times(50.0)
        assert np.array_equal(t1, t2)
        assert not np.array_equal(t1, make(6).times(50.0))
    tr1 = make_trace(PoissonProcess(20.0, seed=1), CLASSES, 50.0, seed=9)
    tr2 = make_trace(PoissonProcess(20.0, seed=1), CLASSES, 50.0, seed=9)
    assert np.array_equal(tr1.arrival, tr2.arrival)
    assert np.array_equal(tr1.cls, tr2.cls)
    # label seed independent of the arrival process seed
    tr3 = make_trace(PoissonProcess(20.0, seed=1), CLASSES, 50.0, seed=10)
    assert np.array_equal(tr1.arrival, tr3.arrival)
    assert not np.array_equal(tr1.cls, tr3.cls)


def test_poisson_rate_recovered_from_trace():
    """Arrival count AND mean inter-arrival gap both recover rate_hz -- the
    gap check guards a silent rate/interval inversion (exponential(rate)
    instead of exponential(1/rate) would pass a smoke test at rate ~ 1)."""
    rate, horizon = 80.0, 2_000.0
    t = PoissonProcess(rate_hz=rate, seed=3).times(horizon)
    assert t.size == pytest.approx(rate * horizon, rel=0.03)
    assert float(np.diff(t).mean()) == pytest.approx(1.0 / rate, rel=0.03)
    assert t[0] >= 0.0 and t[-1] < horizon
    assert np.all(np.diff(t) >= 0)


def test_diurnal_modulation_and_bounds():
    proc = DiurnalProcess(base_rate_hz=50.0, amplitude=0.8, period_s=1_000.0, seed=4)
    assert proc.rate_at(250.0) == pytest.approx(90.0)  # peak = base*(1+amp)
    assert proc.rate_at(750.0) == pytest.approx(10.0)  # trough
    t = proc.times(1_000.0)
    peak_n = ((t >= 100.0) & (t < 400.0)).sum()  # window around the peak
    trough_n = ((t >= 600.0) & (t < 900.0)).sum()
    assert peak_n > 3 * trough_n
    # mean rate over one full period is the base rate
    assert t.size == pytest.approx(50.0 * 1_000.0, rel=0.05)


def test_flash_crowd_burst_rate():
    proc = FlashCrowdProcess(
        base_rate_hz=10.0, bursts=((100.0, 50.0, 200.0),), seed=8
    )
    t = proc.times(400.0)
    in_burst = ((t >= 100.0) & (t < 150.0)).sum()
    outside = t.size - in_burst
    assert in_burst == pytest.approx(50.0 * 210.0, rel=0.08)  # base + extra
    assert outside == pytest.approx(350.0 * 10.0, rel=0.15)
    assert np.all(np.diff(t) >= 0)  # merged streams stay sorted


def test_traffic_validation_errors():
    with pytest.raises(ValueError):
        DeadlineClass("x", deadline_s=0.0)
    with pytest.raises(ValueError):
        DeadlineClass("x", 1.0, target=1.0)  # unattainable under Gaussian offload
    with pytest.raises(ValueError):
        DeadlineClass("x", 1.0, share=0.0)
    with pytest.raises(ValueError):
        PoissonProcess(rate_hz=0.0)
    with pytest.raises(ValueError):
        DiurnalProcess(base_rate_hz=1.0, amplitude=1.5)  # negative rates
    with pytest.raises(ValueError):
        FlashCrowdProcess(base_rate_hz=1.0, bursts=((0.0, -1.0, 5.0),))
    with pytest.raises(ValueError):
        Trace(np.array([2.0, 1.0]), np.array([0, 0]), (CLASSES[0],))  # unsorted
    with pytest.raises(ValueError):
        Trace(np.array([1.0, 2.0]), np.array([0, 3]), (CLASSES[0],))  # bad label
    with pytest.raises(ValueError):
        make_trace(PoissonProcess(1.0), (), 10.0)


def test_trace_deadlines_derive_from_classes():
    tr = make_trace(PoissonProcess(20.0, seed=1), CLASSES, 20.0, seed=2)
    rel = np.array([c.deadline_s for c in CLASSES])
    assert np.array_equal(tr.deadlines(), tr.arrival + rel[tr.cls])
    assert len(tr) == tr.arrival.size


# ---------------------------------------------------------------------------
# serve_trace: the event-driven loop end to end
# ---------------------------------------------------------------------------


def test_serve_trace_validation():
    tr = make_trace(PoissonProcess(20.0, seed=1), CLASSES, 5.0, seed=2)
    with pytest.raises(ValueError):
        serve_trace(tr, LAT[:4], ServeLoopConfig(max_batch=8))  # table too short
    with pytest.raises(ValueError):
        serve_trace(tr, np.stack([LAT, LAT]), ServeLoopConfig())  # rows != bounds+1
    with pytest.raises(ValueError):
        serve_trace(tr, -LAT, ServeLoopConfig())  # non-positive entries
    with pytest.raises(ValueError):
        ServeLoopConfig(max_batch=0)
    with pytest.raises(ValueError):
        ServeLoopConfig(max_delay_s=-1e-3)
    with pytest.raises(ValueError):
        ServeLoopConfig(segment_bounds=(2.0, 1.0))


def test_serve_trace_empty_trace():
    tr = Trace(np.empty(0), np.empty(0, dtype=np.int64), CLASSES)
    out = serve_trace(tr, LAT)
    assert out.n_batches == 0 and len(out.fin) == 0
    s = out.stats()
    assert s["completed"] == 0 and s["p99_latency_s"] == 0.0
    assert s["deadline_met_frac"] == 0.0 and s["mean_batch"] == 0.0


def test_serve_trace_deterministic_and_conserving():
    tr = make_trace(FlashCrowdProcess(30.0, seed=2), CLASSES, 120.0, seed=3)
    cfg = ServeLoopConfig(max_batch=8, channel=CH, seed=11)
    a, b = serve_trace(tr, LAT, cfg), serve_trace(tr, LAT, cfg)
    _assert_served_equal(a, b)
    # conservation: every request is either completed or shed, exactly once
    assert int((~a.shed).sum()) + int(a.shed.sum()) == len(tr)
    assert np.isnan(a.fin[a.shed]).all() and np.isfinite(a.fin[~a.shed]).all()
    assert not a.met[a.shed].any()  # shed requests never meet
    # batch accounting: histogram matches served count and batch count
    assert a.batch_size_counts[0] == 0
    widths = np.arange(a.batch_size_counts.size)
    assert int(a.batch_size_counts @ widths) == int((~a.shed).sum())
    assert int(a.batch_size_counts.sum()) == a.n_batches
    # stats coherence
    s = a.stats()
    assert s["completed"] + s["shed"] == s["n"] == len(tr)
    assert s["deadline_met_frac"] == pytest.approx(a.met.mean())
    per_cls = a.class_stats()
    assert sum(c["n"] for c in per_cls.values()) == len(tr)
    assert sum(c["completed"] for c in per_cls.values()) == s["completed"]


def test_serve_trace_edf_admission_order():
    """A later-arriving tight-deadline request overtakes a queued loose one,
    and the admission cap serves it alone when width 2 would blow its slack."""
    classes = (DeadlineClass("tight", 0.05, target=0.9),
               DeadlineClass("loose", 10.0, target=0.9))
    tr = Trace(np.array([0.0, 0.001]), np.array([1, 0]), classes)  # loose first
    lat = np.array([0.030, 10.0])  # width 2 is hopeless for the tight class
    out = serve_trace(tr, lat, ServeLoopConfig(max_batch=2, max_delay_s=0.01))
    assert not out.shed.any()
    assert out.fin[1] < out.fin[0]  # EDF: tight served first, alone
    assert out.met[1]
    assert out.n_batches == 2 and out.batch_size_counts[1] == 2


def test_serve_trace_sheds_doomed_head_only():
    """A request whose slack cannot clear its target even at b=1 is shed; the
    rest of the queue is served (the per-request PR-5 shed semantics)."""
    classes = (DeadlineClass("doomed", 0.010, target=0.9),
               DeadlineClass("fine", 5.0, target=0.9))
    tr = Trace(np.array([0.0, 0.0]), np.array([0, 1]), classes)
    out = serve_trace(tr, np.array([0.030, 0.035]),
                      ServeLoopConfig(max_batch=2, max_delay_s=0.002))
    assert bool(out.shed[0]) and not bool(out.shed[1])
    assert bool(out.met[1]) and not bool(out.met[0])
    assert out.n_batches == 1 and out.batch_size_counts[1] == 1


def test_serve_trace_no_admission_serves_everything():
    tr = make_trace(FlashCrowdProcess(40.0, seed=5), CLASSES, 60.0, seed=6)
    out = serve_trace(tr, LAT, ServeLoopConfig(max_batch=8, admission=False, channel=CH))
    assert not out.shed.any()
    assert out.stats()["completed"] == len(tr)


def test_serve_trace_segmented_table():
    """Per-segment latency rows apply by formation time: a 10x slower second
    half must push that half's latencies up, and both paths agree."""
    tr = make_trace(PoissonProcess(15.0, seed=7), CLASSES, 60.0, seed=8)
    table = np.stack([LAT, 10.0 * LAT])
    cfg = dict(max_batch=8, segment_bounds=(30.0,), admission=False)
    out = serve_trace(tr, table, ServeLoopConfig(**cfg))
    _assert_served_equal(
        out, serve_trace(tr, table, ServeLoopConfig(**cfg, fast_path=False))
    )
    lat = out.latency()
    first, second = tr.arrival < 29.0, tr.arrival >= 30.0
    assert np.nanmean(lat[second]) > 3.0 * np.nanmean(lat[first])


@settings(max_examples=15, deadline=None)
@given(
    rate=st.floats(min_value=5.0, max_value=120.0),
    seed=st.integers(min_value=0, max_value=10_000),
    mb=st.integers(min_value=2, max_value=8),
    sig=st.sampled_from([0.0, 2e-3, 9e-3]),
    admit=st.sampled_from([True, False]),
)
def test_property_fast_path_bit_identical(rate, seed, mb, sig, admit):
    """The vectorized fast path and the scalar event loop are the same
    function: identical fins, sheds, mets, and batch histograms, across
    underload, overload, noisy channels, and both admission policies."""
    tr = make_trace(PoissonProcess(rate, seed=seed), CLASSES, 25.0, seed=seed + 1)
    base = dict(max_batch=mb, admission=admit, seed=seed,
                channel=OffloadChannel(rate_bps=100e6, sigma_s=sig))
    fast = serve_trace(tr, LAT, ServeLoopConfig(**base, fast_path=True))
    slow = serve_trace(tr, LAT, ServeLoopConfig(**base, fast_path=False))
    _assert_served_equal(fast, slow)


@settings(max_examples=15, deadline=None)
@given(
    rate=st.floats(min_value=5.0, max_value=80.0),
    seed=st.integers(min_value=0, max_value=10_000),
    mb=st.integers(min_value=1, max_value=8),
)
def test_property_deterministic_channel_admits_only_winners(rate, seed, mb):
    """With sigma=0 the reliability model is a step function, so admission
    becomes a theorem: every admitted request meets its deadline, always."""
    tr = make_trace(PoissonProcess(rate, seed=seed), CLASSES, 20.0, seed=seed + 1)
    out = serve_trace(
        tr, LAT, ServeLoopConfig(max_batch=mb, channel=CH0, seed=seed)
    )
    assert out.met[~out.shed].all()
    # and the loop conserves requests under any load
    assert int(out.shed.sum()) + int((~out.shed).sum()) == len(tr)


@settings(max_examples=20, deadline=None)
@given(
    slack_scale=st.floats(min_value=0.5, max_value=1.5),
    sig=st.sampled_from([1e-3, 5e-3, 9e-3]),
    target=st.floats(min_value=0.6, max_value=0.999),
)
def test_property_singleton_admission_matches_choose_batch_size(
    slack_scale, sig, target
):
    """For an isolated request the trace loop's margin test IS
    choose_batch_size's b=1 feasibility: both shed or both admit, on either
    side of the required_slack threshold."""
    ch = OffloadChannel(rate_bps=100e6, sigma_s=sig)
    delay = 0.002
    # relative deadline scaled around the exact singleton threshold
    rel_dl = (required_slack(ch, LAT[0], target) + delay) * slack_scale
    cls = (DeadlineClass("c", rel_dl, target=target),)
    tr = Trace(np.array([0.0]), np.array([0]), cls)
    out = serve_trace(
        tr, LAT, ServeLoopConfig(max_batch=8, max_delay_s=delay, channel=ch)
    )
    # slack available once the batch forms (the head waited max_delay)
    expect_admit = (
        choose_batch_size(
            lambda b: LAT[b - 1], rel_dl - delay, ch, target=target, max_batch=1
        )
        == 1
    )
    assert bool(out.shed[0]) == (not expect_admit)


def test_serve_trace_forms_batch_when_queue_fills_mid_wait():
    """The queue reaching max_batch *during* the head's delay wait must form
    the batch at the max_batch-th arrival (BatchingEngine's launch-when-full
    rule), not at the head's full delay budget -- on both code paths."""
    cls = (DeadlineClass("c", 10.0, target=0.9),)
    cfg = dict(max_batch=3, max_delay_s=0.5, admission=False)
    # fills at t=0.2 < 0.0+0.5: one width-3 batch formed at 0.2
    tr = Trace(np.array([0.0, 0.1, 0.2]), np.zeros(3, dtype=np.int64), cls)
    out = serve_trace(tr, LAT, ServeLoopConfig(**cfg))
    assert out.n_batches == 1 and out.batch_size_counts[3] == 1
    assert np.allclose(out.fin, 0.2 + LAT[2])
    _assert_served_equal(
        out, serve_trace(tr, LAT, ServeLoopConfig(**cfg, fast_path=False))
    )
    # the third arrival misses the budget: the head's delay still rules and
    # the late request becomes its own batch
    tr2 = Trace(np.array([0.0, 0.1, 0.9]), np.zeros(3, dtype=np.int64), cls)
    out2 = serve_trace(tr2, LAT, ServeLoopConfig(**cfg))
    assert out2.n_batches == 2
    assert out2.batch_size_counts[2] == 1 and out2.batch_size_counts[1] == 1
    assert np.allclose(out2.fin[:2], 0.5 + LAT[1])
    assert np.allclose(out2.fin[2], 0.9 + 0.5 + LAT[0])
    _assert_served_equal(
        out2, serve_trace(tr2, LAT, ServeLoopConfig(**cfg, fast_path=False))
    )


def _engine_reference(tr, lat, mb, max_delay):
    """Step-by-step BatchingEngine + VirtualClock reference for serve_trace
    (admission off, deterministic channel): submit each arrival at its exact
    arrival instant, launch by eng.ready() gated on a single busy server, and
    charge lat[b-1] of virtual service time per width-b batch.  Returns
    (fin per request, n_batches, batch-size histogram)."""
    clk = VirtualClock()
    eng = BatchingEngine(
        jax.jit(lambda b: b),
        ServeConfig(max_batch=mb, max_delay_s=max_delay, pad_to_max=False),
        clock=clk,
    )
    arr = tr.arrival
    rel = np.array([c.deadline_s for c in tr.classes])[tr.cls]
    n = len(tr)
    fin = np.full(n, np.nan)
    counts = np.zeros(mb + 1, dtype=np.int64)
    n_batches = 0
    i = 0
    free = 0.0
    while i < n or eng.queue:
        now = clk.now()
        while i < n and arr[i] <= now:
            eng.submit(jnp.zeros(()), deadline_s=float(rel[i]))
            i += 1
        if eng.queue and now >= free:
            if eng.ready():
                batch = eng.step()
                b = len(batch)
                t_fin = now + lat[b - 1]
                for r in batch:
                    fin[r.rid - 1] = t_fin  # rids: 1-based submission order
                free = t_fin
                counts[b] += 1
                n_batches += 1
                continue
            exp = eng._oldest_pending().arrival + max_delay
            if exp <= now:
                # fp edge: ready()'s (now - a) >= delay can round an ulp
                # below delay at the nominal expiry a + delay -- crawl ulps
                # until the engine agrees (1-2 iterations), never past it
                clk.advance_to(float(np.nextafter(now, np.inf)))
                continue
        cands = []
        if i < n:
            cands.append(float(arr[i]))
        if eng.queue:
            if free > now:
                # blocked on the busy server: the next decision instant is
                # free (the head's expiry may already be behind us)
                cands.append(free)
            else:
                cands.append(eng._oldest_pending().arrival + max_delay)
        clk.advance_to(min(cands))
    return fin, n_batches, counts


@settings(max_examples=12, deadline=None)
@given(
    rate=st.floats(min_value=20.0, max_value=200.0),
    seed=st.integers(min_value=0, max_value=10_000),
    mb=st.integers(min_value=2, max_value=6),
    delay=st.sampled_from([0.005, 0.02, 0.1]),
    fast=st.sampled_from([True, False]),
)
def test_property_matches_batching_engine_reference(rate, seed, mb, delay, fast):
    """Both serve_trace code paths replicate the live BatchingEngine's
    semantics on random traces -- same batches formed at the same times (full
    -- including filling mid-wait -- or head-delay-expired), same EDF
    membership, same completions.  High rates with small max_batch make the
    full-queue-mid-wait case the dominant regime."""
    tr = make_trace(PoissonProcess(rate, seed=seed), CLASSES, 4.0, seed=seed + 1)
    cfg = ServeLoopConfig(
        max_batch=mb, max_delay_s=delay, admission=False, fast_path=fast
    )
    out = serve_trace(tr, LAT, cfg)
    ref_fin, ref_batches, ref_counts = _engine_reference(tr, LAT, mb, delay)
    assert out.n_batches == ref_batches
    assert np.array_equal(out.batch_size_counts, ref_counts)
    assert np.allclose(out.fin, ref_fin, rtol=0.0, atol=1e-9, equal_nan=True)


def test_serve_trace_offload_noise_is_seeded():
    tr = make_trace(PoissonProcess(30.0, seed=1), CLASSES, 30.0, seed=2)
    a = serve_trace(tr, LAT, ServeLoopConfig(channel=CH, seed=5))
    b = serve_trace(tr, LAT, ServeLoopConfig(channel=CH, seed=5))
    c = serve_trace(tr, LAT, ServeLoopConfig(channel=CH, seed=6))
    _assert_served_equal(a, b)
    assert not np.array_equal(a.fin, c.fin, equal_nan=True)  # noise seed moves fins
    # deterministic channel: seed is inert
    d = serve_trace(tr, LAT, ServeLoopConfig(channel=CH0, seed=5))
    e = serve_trace(tr, LAT, ServeLoopConfig(channel=CH0, seed=99))
    _assert_served_equal(d, e)


def test_serve_trace_flash_crowd_shedding_protects_served_requests():
    """Under a burst at ~3x capacity, shedding keeps admitted requests on
    deadline while the no-shed baseline queues everyone into missing."""
    tr = make_trace(FlashCrowdProcess(10.0, bursts=((10.0, 20.0, 300.0),), seed=4),
                    CLASSES, 60.0, seed=5)
    shed = serve_trace(tr, LAT, ServeLoopConfig(max_batch=8, channel=CH0))
    noshed = serve_trace(
        tr, LAT, ServeLoopConfig(max_batch=8, channel=CH0, admission=False)
    )
    assert shed.stats()["shed_rate"] > 0.2  # the burst forces real shedding
    assert shed.stats()["met_of_admitted"] == 1.0  # sigma=0: admitted == met
    for name in ("premium", "standard", "bulk"):
        assert (
            shed.class_stats()[name]["deadline_met_frac"]
            >= noshed.class_stats()[name]["deadline_met_frac"]
        )


def test_launcher_serves_smoke_vgg16(monkeypatch, capsys):
    """``python -m repro.launch.serve --smoke`` serves the reduced VGG-16
    through the HALP plan and names the backend it served on."""
    from repro.launch import serve as launcher

    monkeypatch.setattr(launcher, "enable_compile_cache", lambda: None)
    # one batch width, so one compile of the plan
    stats = launcher.main(["--smoke", "--requests", "2", "--max-batch", "2"])
    assert stats["completed"] == 2
    out = capsys.readouterr().out
    dev = jax.devices()[0]
    assert f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}" in out
    assert "serving vgg16 at 64 px through the HALP plan" in out
    for name, n in (("build.init", 1), ("build.plan", 1), ("serve.step", 1), ("serve.call", 1)):
        assert f"span {name}: n={n} mean=" in out


def test_launcher_model_matches_plain_forward():
    """The launcher's served function (plan_halp -> run_plan -> head) gives
    the logits of the plain single-device forward."""
    from repro.launch.serve import build_model
    from repro.models import vgg

    cfg, params, fn = build_model("vgg16", smoke=True)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, cfg.img_res, cfg.img_res, 3))
    np.testing.assert_allclose(
        np.asarray(fn(x)), np.asarray(vgg.apply(params, cfg, x)), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("env_dir", [None, "/cache/placed/outside"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` places the cache from outside and the
    helper sets nothing; without it the cache is ``<checkout>/.jax_cache``."""
    from pathlib import Path

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            root = Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
        else:
            assert got == before  # left to JAX, which read the variable itself
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
