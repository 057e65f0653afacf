"""Runtime tests: the serving engine and its batch-size admission policy."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.runtime import BatchingEngine, Request, ServeConfig, choose_batch_size
from repro.core.reliability import OffloadChannel, service_reliability


def test_serve_config_rejects_shed_as_max_batch():
    """Building an engine on an admission result of 0 (shed) would busy-loop
    taking empty batches forever; ServeConfig refuses it loudly."""
    with pytest.raises(ValueError, match="shed"):
        ServeConfig(max_batch=0)


def test_batching_engine_es_timing_hook():
    """observe_es_time forwards per-ES chunk timings to the wired observer
    (the compute half of the joint replan loop); without a wire it is a
    no-op."""
    seen = []
    eng = BatchingEngine(
        jax.jit(lambda b: b),
        ServeConfig(max_batch=2),
        es_observer=lambda es, fl, dt: seen.append((es, fl, dt)),
    )
    eng.observe_es_time("e1", 3.2e9, 0.004)
    eng.observe_es_time("e2", 1.6e9, 0.004)
    assert seen == [("e1", 3.2e9, 0.004), ("e2", 1.6e9, 0.004)]
    # unwired engine: silently ignored
    BatchingEngine(jax.jit(lambda b: b), ServeConfig()).observe_es_time("e1", 1.0, 1.0)


def test_batching_engine_deadlines():
    calls = {"n": 0}

    inner = jax.jit(lambda b: jnp.sum(b, axis=(1, 2, 3)))

    def fn(batch):
        calls["n"] += 1  # counts batch executions (fn itself is not traced)
        return inner(batch)

    eng = BatchingEngine(fn, ServeConfig(max_batch=4))
    for i in range(10):
        eng.submit(jnp.ones((4, 4, 3)) * i, deadline_s=5.0)
    stats = eng.run_until_drained()
    assert stats["completed"] == 10
    assert stats["deadline_met_frac"] == 1.0
    assert calls["n"] == 3  # 4 + 4 + 2(padded)


def test_batching_engine_edf_order():
    """Earliest-deadline-first: tight-deadline requests run in the first batch."""
    eng = BatchingEngine(jax.jit(lambda b: b), ServeConfig(max_batch=2))
    r_loose = eng.submit(jnp.zeros(()), deadline_s=10.0)
    r_tight = eng.submit(jnp.zeros(()), deadline_s=0.5)
    r_mid = eng.submit(jnp.zeros(()), deadline_s=2.0)
    first = eng.step()
    assert {r.rid for r in first} == {r_tight, r_mid}


def test_choose_batch_size_policy():
    """Bigger channels admit bigger batches; the policy is monotone."""
    lat = lambda b: 2e-3 + 1e-3 * b  # linear latency model
    ch_fast = OffloadChannel(rate_bps=100e6, sigma_s=1e-3)
    ch_slow = OffloadChannel(rate_bps=35e6, sigma_s=5e-3)
    b_fast = choose_batch_size(lat, 4.0 / 30.0, ch_fast, target=0.999, max_batch=16)
    b_slow = choose_batch_size(lat, 4.0 / 30.0, ch_slow, target=0.999, max_batch=16)
    assert b_fast >= b_slow
    assert 1 <= b_slow <= 16


def test_request_declares_result_field():
    """``BatchingEngine.step`` assigns per-request outputs; the dataclass must
    declare the field (not rely on instance-attribute injection)."""
    names = {f.name for f in dataclasses.fields(Request)}
    assert "result" in names
    assert Request(deadline=1.0, rid=1).result is None


def test_service_reliability_sigma_zero_is_a_step():
    """sigma=0 degenerates to a deterministic deadline check (boundary met)."""
    ch = OffloadChannel(rate_bps=40e6, sigma_s=0.0)  # mu = 4 Mbit / 40 Mbps = 0.1 s
    assert service_reliability(ch, 0.0333, 4.0 / 30.0) == 1.0  # slack ~0, met
    assert service_reliability(ch, 0.0334, 4.0 / 30.0) == 0.0
    assert service_reliability(ch, 0.0, 4.0 / 30.0) == 1.0


def test_choose_batch_size_sigma_zero_deterministic():
    """With a deterministic channel the policy picks the exact cutoff batch."""
    ch = OffloadChannel(rate_bps=40e6, sigma_s=0.0)  # mu = 0.1 s
    lat = lambda b: 5e-3 * b
    # feasible iff 0.1 + 0.005 b <= 4/30 = 0.1333... i.e. b <= 6
    assert choose_batch_size(lat, 4.0 / 30.0, ch, target=0.99999, max_batch=16) == 6


def test_choose_batch_size_unreachable_target_sheds():
    """When no batch size clears the reliability target, the policy returns 0
    (shed/reject) -- the historical fallback of 1 silently admitted requests
    that were already known to miss their deadline."""
    ch = OffloadChannel(rate_bps=40e6, sigma_s=5e-3)
    assert choose_batch_size(lambda b: 10.0, 4.0 / 30.0, ch, max_batch=16) == 0
    # a deterministic channel whose offload alone blows the deadline: even
    # b=1 with zero inference time is infeasible -> shed
    ch0 = OffloadChannel(rate_bps=1e6, sigma_s=0.0)  # mu = 4 s >> D
    assert choose_batch_size(lambda b: 0.0, 4.0 / 30.0, ch0, max_batch=4) == 0


def test_choose_batch_size_non_monotone_latency():
    """A latency spike at a middle batch size must not mask larger feasible
    batches: the policy returns the *largest* batch clearing the target."""
    ch = OffloadChannel(rate_bps=40e6, sigma_s=0.0)  # mu = 0.1 s
    lat = lambda b: 0.2 if b == 3 else 1e-3 * b  # b=3 infeasible, b=8 fine
    assert choose_batch_size(lat, 4.0 / 30.0, ch, target=0.99999, max_batch=8) == 8


def test_batching_engine_observer_sees_executed_width():
    """The engine reports (executed batch width, elapsed) per batch -- the
    feedback hook the online re-planner calibrates against.  With pad_to_max
    the final short batch runs (and is reported) at the padded width, since
    that is the size the measured latency corresponds to."""
    seen = []
    eng = BatchingEngine(
        jax.jit(lambda b: b),
        ServeConfig(max_batch=4),
        observer=lambda n, dt: seen.append((n, dt)),
    )
    for i in range(10):
        eng.submit(jnp.ones(()) * i, deadline_s=5.0)
    eng.run_until_drained()
    assert [n for n, _ in seen] == [4, 4, 4]  # last batch padded 2 -> 4
    assert all(dt >= 0.0 for _, dt in seen)

    seen.clear()
    eng = BatchingEngine(
        jax.jit(lambda b: b),
        ServeConfig(max_batch=4, pad_to_max=False),
        observer=lambda n, dt: seen.append((n, dt)),
    )
    for i in range(10):
        eng.submit(jnp.ones(()) * i, deadline_s=5.0)
    eng.run_until_drained()
    assert [n for n, _ in seen] == [4, 4, 2]  # unpadded: true sizes
