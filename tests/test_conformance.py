"""Closed-form vs DES conformance grid (paper eqs. 16-20 / 22-23).

One systematic cross-validation replaces the per-feature spot checks that used
to live in test_schedule/test_topology: every (cluster size, link/platform
skew, task count) cell asserts the closed-form recursion stays an **upper
bound** on the exact discrete-event simulation, within a **pinned slack** --
the bound's measured looseness at the time it was pinned.  A future change
that silently loosens (or breaks the bound direction of) either engine fails
the grid immediately.

Also pinned here: the tightened multi-task host term (``multitask_bound=
"list"``) is never looser than the paper's eq. 22 (``"eq22"``) anywhere on
the grid, and strictly tighter where K > 1 zones meet asymmetric links.

The vectorized DES (``Sim.run_batch``) and the batched candidate evaluator
(``events.HalpBatchEvaluator``: plan layouts + DAG templates) must match the
scalar engines to float *equality* -- not closeness -- on every cell: the
online planner's batched fast path is only trustworthy if it is the same
simulator, and any drift in the layout/template factorisation shows up here
as a single-bit diff.  Hypothesis property tests extend the same claim to
random plans and random per-resource slowdowns.
"""
import dataclasses
import math

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (
    AGX_XAVIER,
    GTX_1080TI,
    SCHEME_HALO,
    SCHEME_NP,
    SCHEMES,
    CollabTopology,
    Link,
    SchemeBatchEvaluator,
    halp_closed_form,
    plan_scheme,
    simulate_halp,
    simulate_scheme,
    stage_scheme_options,
    stage_spans,
    standalone_time,
    vgg16_geom,
)
from repro.core.events import HalpBatchEvaluator, MultitaskBatchEvaluator
from repro.core.optimizer import evaluate_plan
from repro.core.simulator import Sim

NET = vgg16_geom()

# Bound-direction tolerance: the closed form must not dip below the DES by
# more than float noise anywhere on the grid.
LOWER_TOL = 1e-9

SKEW_SCALES = (1.0, 0.5, 0.8, 0.3, 0.65)


def sym_topology(n: int, platform=GTX_1080TI) -> CollabTopology:
    return CollabTopology.symmetric(platform, Link(40e9), n_secondaries=n)


def skew_topology(n: int) -> CollabTopology:
    """Heterogeneous platforms (x1.0 .. x0.3) with alternating 40/10 Gbps
    links -- the regime where eq. 22's worst-case terms are loosest."""
    secs = tuple(f"e{j}" for j in range(1, n + 1))
    platforms = {"e0": GTX_1080TI}
    links = {}
    for j, (s, scale) in enumerate(zip(secs, SKEW_SCALES)):
        platforms[s] = GTX_1080TI.scaled(scale, f"es x{scale:g}")
        rate = 10e9 if j % 2 else 40e9
        links[("e0", s)] = Link(rate)
        links[(s, "e0")] = Link(rate)
    return CollabTopology(
        host="e0", secondaries=secs, platforms=platforms,
        links=links, default_link=Link(40e9),
    )


TOPOLOGIES = {
    "sym": sym_topology,
    "skew": skew_topology,
    "sym-agx": lambda n: sym_topology(n, AGX_XAVIER),
}

# Pinned upper slack per cell: measured closed-form/DES ratio at pin time
# (see the PR that introduced this file) plus ~3-5% headroom.  The bound
# loosens with zone count K and link skew; that structure should survive
# refactors -- a cell blowing its slack means an engine changed behaviour.
UPPER_SLACK = {
    # (n_secondaries, kind, n_tasks): max allowed cf/ev
    (2, "sym", 1): 1.05, (2, "sym", 4): 1.11,
    (2, "skew", 1): 1.06, (2, "skew", 4): 1.26,
    (2, "sym-agx", 1): 1.04, (2, "sym-agx", 4): 1.05,
    (3, "sym", 1): 1.09, (3, "sym", 4): 1.11,
    (3, "skew", 1): 1.15, (3, "skew", 4): 1.49,
    (3, "sym-agx", 1): 1.05, (3, "sym-agx", 4): 1.05,
    (5, "sym", 1): 1.11, (5, "sym", 4): 1.08,
    (5, "skew", 1): 1.14, (5, "skew", 4): 1.22,
    (5, "sym-agx", 1): 1.05, (5, "sym-agx", 4): 1.05,
}

GRID = sorted(UPPER_SLACK)


@pytest.mark.parametrize("n_sec,kind,n_tasks", GRID)
def test_closed_form_upper_bounds_des_within_pinned_slack(n_sec, kind, n_tasks):
    topo = TOPOLOGIES[kind](n_sec)
    cf = halp_closed_form(NET, topology=topo, n_tasks=n_tasks)["total"]
    ev = simulate_halp(NET, topology=topo, n_tasks=n_tasks)["total"]
    assert cf >= ev * (1.0 - LOWER_TOL), (
        f"closed form lost the upper-bound property: cf={cf} < ev={ev}"
    )
    slack = UPPER_SLACK[(n_sec, kind, n_tasks)]
    assert cf <= ev * slack, (
        f"closed form loosened past its pinned slack {slack}: cf/ev={cf / ev:.4f}"
    )


@pytest.mark.parametrize("n_sec,kind,n_tasks", GRID)
def test_tightened_bound_never_looser_than_eq22(n_sec, kind, n_tasks):
    """The list-scheduling multi-task host term is term-by-term <= eq. 22,
    and identical to it for a single task (where both reduce to eq. 18)."""
    topo = TOPOLOGIES[kind](n_sec)
    tight = halp_closed_form(NET, topology=topo, n_tasks=n_tasks)["total"]
    legacy = halp_closed_form(
        NET, topology=topo, n_tasks=n_tasks, multitask_bound="eq22"
    )["total"]
    assert tight <= legacy + 1e-15, (tight, legacy)
    if n_tasks == 1:
        assert tight == legacy


def test_tightened_bound_strictly_tighter_where_k_gt_1():
    """With K > 1 zones and skewed links the tightening is strict (the whole
    point of generalising eq. 22 for the multi-zone case)."""
    for n_sec in (3, 5):
        topo = skew_topology(n_sec)
        tight = halp_closed_form(NET, topology=topo, n_tasks=4)["total"]
        legacy = halp_closed_form(
            NET, topology=topo, n_tasks=4, multitask_bound="eq22"
        )["total"]
        assert tight < legacy, (n_sec, tight, legacy)


def test_multitask_bound_rejects_unknown_mode():
    with pytest.raises(ValueError, match="multitask_bound"):
        halp_closed_form(NET, GTX_1080TI, Link(40e9), multitask_bound="magic")


# ---------------------------------------------------------------------------
# Vectorized DES + batched evaluator: float equality with the scalar engines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_sec,kind,n_tasks", GRID)
def test_run_batch_matches_scalar_sim(n_sec, kind, n_tasks):
    """Both ``run_batch`` code paths (plain-float small-batch and numpy
    wide-batch) must reproduce the scalar ``Sim.run`` makespan exactly."""
    topo = TOPOLOGIES[kind](n_sec)
    res = simulate_halp(NET, topology=topo, n_tasks=n_tasks)
    sim = res["sim"]
    small = sim.run_batch()  # B=1: the plain-float path
    assert float(small.makespan[0]) == res["total"]
    durations = np.array([[job.duration for job in sim.jobs]])
    wide = sim.run_batch(np.repeat(durations, 40, axis=0))  # forces numpy path
    assert all(float(m) == res["total"] for m in wide.makespan)


@pytest.mark.parametrize("n_sec,kind,n_tasks", GRID)
def test_batched_evaluator_matches_evaluate_plan(n_sec, kind, n_tasks):
    """Layout + template + run_batch candidate scores == plan build + DAG
    build + scalar DES, bit for bit, across ratios/overlap candidates."""
    topo = TOPOLOGIES[kind](n_sec)
    n = topo.n_secondaries
    skewed = tuple(j + 1.0 for j in range(n))
    total = sum(skewed)
    cands = [
        (tuple(1.0 / n for _ in range(n)), 4),
        (tuple(r / total for r in skewed), 2),
        (tuple(r / total for r in reversed(skewed)), 8),
    ]
    evaluator = HalpBatchEvaluator(NET, topo, n_tasks=n_tasks)
    batched = evaluator.evaluate(cands)
    scalar = [evaluate_plan(NET, topo, r, w, n_tasks=n_tasks) for r, w in cands]
    assert batched == scalar


def test_multitask_evaluator_matches_simulate_placement():
    """The shared-pool (physical-resource) template path must equal the
    scalar multi-task DES on makespan, mean delay, and per-task finishes."""
    from repro.core.placement import shared_plan_placement, simulate_placement

    pool = skew_topology(5).with_links({})
    ev = MultitaskBatchEvaluator(NET, pool)
    groups = (("e1", "e4"), ("e2", "e3", "e5"))
    res = ev.evaluate([groups])[0]
    from repro.core.partition import plan_halp_topology

    plans = [
        plan_halp_topology(NET, pool.sub_topology(g), overlap_rows=4)
        for g in groups
    ]
    from repro.core.placement import _simulate_plans

    ref = _simulate_plans(NET, plans, pool)
    assert res["total"] == ref["total"]
    assert res["avg_delay"] == ref["avg_delay"]
    assert res["per_task_finish"] == tuple(ref["per_task_finish"])


@given(
    n_sec=st.integers(min_value=2, max_value=4),
    overlap=st.sampled_from([2, 4, 6, 8]),
    data=st.data(),
)
@settings(max_examples=10, deadline=None)
def test_run_batch_matches_scalar_under_random_plans_and_slowdowns(
    n_sec, overlap, data
):
    """Property: for random ratios, overlap widths, and per-resource slowdown
    factors, the vectorized forward pass equals the scalar DES exactly."""
    raw = [
        data.draw(st.integers(min_value=1, max_value=9), label=f"r{j}")
        for j in range(n_sec)
    ]
    ratios = tuple(r / sum(raw) for r in raw)
    topo = skew_topology(n_sec)
    res = simulate_halp(NET, topology=topo, ratios=ratios, overlap_rows=overlap)
    sim = res["sim"]
    resources = sorted({job.resource for job in sim.jobs})
    for res_name in resources[:: max(1, len(resources) // 3)]:
        sim.slowdown[res_name] = 1.0 + data.draw(
            st.integers(min_value=0, max_value=30), label="slow"
        ) / 10.0
    scalar = sim.run()
    batch = sim.run_batch()
    assert float(batch.makespan[0]) == scalar
    # and the wide-batch numpy path agrees with itself and the scalar run
    durations = np.array([[job.duration for job in sim.jobs]])
    wide = sim.run_batch(np.repeat(durations, 40, axis=0))
    assert all(float(m) == scalar for m in wide.makespan)


@given(
    n_sec=st.integers(min_value=2, max_value=4),
    overlap=st.sampled_from([2, 4, 6, 8]),
    n_tasks=st.sampled_from([1, 3]),
    data=st.data(),
)
@settings(max_examples=10, deadline=None)
def test_batched_evaluator_property(n_sec, overlap, n_tasks, data):
    """Property: batched candidate scores equal the scalar pricing path for
    random ratio simplex points (including heavily skewed, auto-reducing and
    infeasible ones, which must price +inf identically)."""
    raw = [
        data.draw(st.integers(min_value=0, max_value=9), label=f"r{j}")
        for j in range(n_sec)
    ]
    if sum(raw) == 0:
        raw[0] = 1
    ratios = tuple(r / sum(raw) for r in raw)
    topo = skew_topology(n_sec)
    evaluator = HalpBatchEvaluator(NET, topo, n_tasks=n_tasks)
    batched = evaluator.evaluate([(ratios, overlap)])
    scalar = [evaluate_plan(NET, topo, ratios, overlap, n_tasks=n_tasks)]
    assert batched == scalar


# ---------------------------------------------------------------------------
# Per-stage partitioning schemes: mixed-scheme DAG pricing + lossless execution
# ---------------------------------------------------------------------------
#
# The scheme DAG (``events.build_scheme_dag``) must be the *same simulator* as
# the legacy HALP DAG wherever the spaces coincide: an all-halo assignment
# prices float-identically to ``evaluate_plan`` at n_tasks=1 (at n_tasks>1 the
# scheme DAG serialises segment barriers through the host FIFO, a deliberately
# tighter ordering, so equality is only claimed for the single-task pricing
# the planner search uses).  The batched candidate evaluator must equal the
# scalar engine to float equality on every scheme cell, mirroring the
# HalpBatchEvaluator contract above.

SCHEME_RATIOS = (0.5, 0.3, 0.2)


def _scheme_assignment(net, scheme_kind):
    spans = stage_spans(net)
    options = [stage_scheme_options(net, sp, SCHEMES) for sp in spans]
    if scheme_kind == "halo":
        return tuple(SCHEME_HALO for _ in spans)
    if scheme_kind == "non_penetrative":
        return tuple(SCHEME_NP if SCHEME_NP in o else o[0] for o in options)
    assert scheme_kind == "mixed"
    return tuple(
        (SCHEME_NP if si % 2 else SCHEME_HALO)
        if (SCHEME_NP if si % 2 else SCHEME_HALO) in opts
        else opts[0]
        for si, opts in enumerate(options)
    )


@pytest.mark.parametrize("kind", ["sym", "skew"])
@pytest.mark.parametrize("scheme_kind", ["halo", "non_penetrative", "mixed"])
def test_scheme_grid_batched_matches_scalar(scheme_kind, kind):
    """Every {scheme} x {topology} cell: the batched scheme evaluator equals
    the scalar DES bit for bit, and the all-halo cells collapse onto the
    legacy HALP pricing path exactly."""
    topo = TOPOLOGIES[kind](3)
    assignment = _scheme_assignment(NET, scheme_kind)
    total = simulate_scheme(
        NET, topo, ratios=SCHEME_RATIOS, overlap_rows=4, assignment=assignment
    )["total"]
    assert math.isfinite(total) and total > 0
    batched = SchemeBatchEvaluator(NET, topo).evaluate(
        [(SCHEME_RATIOS, 4, assignment)]
    )
    assert batched == [total]
    if scheme_kind == "halo":
        assert total == evaluate_plan(NET, topo, SCHEME_RATIOS, 4, n_tasks=1)


def test_all_halo_scheme_plan_is_the_halp_plan():
    """Choosing halo_segment for every stage must reproduce
    ``plan_halp_topology``'s plan *exactly* -- the scheme layer is a strict
    superset of the legacy planner, not a fork of it."""
    from repro.core import plan_halp_topology

    topo = skew_topology(3)
    sp = plan_scheme(
        NET, topo, overlap_rows=4, ratios=SCHEME_RATIOS,
        assignment=_scheme_assignment(NET, "halo"),
    )
    hp = plan_halp_topology(NET, topo, ratios=SCHEME_RATIOS, overlap_rows=4)
    assert len(sp.segments) == 1  # all-halo stages fuse into one segment
    assert sp.segments[0].scheme == SCHEME_HALO
    sub = sp.halo_plans[0]
    # the segment subnet is the same geometry under a span-suffixed name
    assert sub.net.layers == hp.net.layers
    assert sub.net.in_rows == hp.net.in_rows
    assert dataclasses.replace(sub, net=hp.net) == hp


@given(overlap=st.sampled_from([2, 4, 8]), data=st.data())
@settings(max_examples=10, deadline=None)
def test_scheme_batched_evaluator_property(overlap, data):
    """Property: random per-stage scheme assignments and random ratio simplex
    points price float-identically through the batched evaluator and the
    scalar scheme DES."""
    spans = stage_spans(NET)
    assignment = tuple(
        data.draw(st.sampled_from(stage_scheme_options(NET, sp, SCHEMES)), label=f"s{si}")
        for si, sp in enumerate(spans)
    )
    raw = [
        data.draw(st.integers(min_value=1, max_value=9), label=f"r{j}")
        for j in range(3)
    ]
    ratios = tuple(r / sum(raw) for r in raw)
    topo = skew_topology(3)
    scalar = simulate_scheme(
        NET, topo, ratios=ratios, overlap_rows=overlap, assignment=assignment
    )["total"]
    batched = SchemeBatchEvaluator(NET, topo).evaluate([(ratios, overlap, assignment)])
    assert batched == [scalar]


_EXEC_CACHE: dict = {}


def _exec_setup():
    """Small runnable VGG (module-level cache; jax imports lazily so the
    pricing-only tests above stay importable without touching jax)."""
    if not _EXEC_CACHE:
        import jax

        from repro.models import vgg

        cfg = vgg.VGGConfig(img_res=64, width_mult=0.125, num_classes=10)
        params = vgg.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
        _EXEC_CACHE.update(
            cfg=cfg, params=params, x=x, ref=vgg.features(params, cfg, x)
        )
    return _EXEC_CACHE


@given(overlap=st.sampled_from([2, 4]), data=st.data())
@settings(max_examples=6, deadline=None)
def test_random_mixed_scheme_plans_execute_lossless(overlap, data):
    """Property: random mixed-scheme plans (random per-stage assignment drawn
    from each stage's legal vocabulary, random capacity ratios) execute
    through ``run_plan`` to the single-device reference within float noise --
    the executable-losslessness backstop for every scheme, not just halo."""
    from repro.models import vgg
    from repro.spatial import run_plan

    env = _exec_setup()
    net = env["cfg"].geom()
    spans = stage_spans(net)
    assignment = tuple(
        data.draw(st.sampled_from(stage_scheme_options(net, sp, SCHEMES)), label=f"s{si}")
        for si, sp in enumerate(spans)
    )
    raw = [
        data.draw(st.integers(min_value=1, max_value=3), label=f"r{j}")
        for j in range(2)
    ]
    ratios = tuple(r / sum(raw) for r in raw)
    topo = sym_topology(2)
    plan = plan_scheme(
        net, topo, overlap_rows=overlap, ratios=ratios, assignment=assignment
    )
    out = run_plan(plan, env["params"]["features"], vgg.apply_layer, env["x"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(env["ref"]), rtol=2e-5, atol=2e-5
    )


def test_joint_scheme_search_engine_equality():
    """Optimizer engine-equality extended to the enlarged (scheme-per-stage,
    ratios, overlap) space: batched and scalar engines return the identical
    plan, score, and assignment, and under an eval budget they also spend the
    identical number of evaluations before cutting."""
    from repro.core import optimize_plan

    net = vgg16_geom(in_rows=64)
    topo = skew_topology(2)
    kw = dict(overlap_choices=(4,), max_rounds=2, schemes=SCHEMES)
    rb = optimize_plan(net, topo, engine="batched", **kw)
    rs = optimize_plan(net, topo, engine="scalar", **kw)
    assert rb.makespan == rs.makespan
    assert rb.ratios == rs.ratios
    assert rb.overlap_rows == rs.overlap_rows
    assert rb.schemes == rs.schemes
    assert rb.plan == rs.plan
    bb = optimize_plan(net, topo, engine="batched", eval_budget=8, **kw)
    bs = optimize_plan(net, topo, engine="scalar", eval_budget=8, **kw)
    assert bb.makespan == bs.makespan
    assert bb.schemes == bs.schemes
    assert bb.evaluations == bs.evaluations == 8  # the budget binds (full run: 11)


@pytest.mark.parametrize("n_tasks", [1, 4])
def test_degenerate_single_es_exact(n_tasks):
    """N = 1 cell of the grid: no collaboration at all.  The closed form is
    t_pre x n_tasks (eq. 21's denominator), and a single-resource DES chain
    reproduces it exactly -- both engines share the FLOP model, so this cell
    must be equality, not a bound."""
    t_pre = standalone_time(NET, GTX_1080TI)
    sim = Sim()
    prev = None
    sizes = NET.sizes()
    for _ in range(n_tasks):
        for i, g in enumerate(NET.layers):
            prev = sim.add(
                f"g{i}", "e0",
                GTX_1080TI.compute_time(g.flops_per_out_row(sizes[i + 1]) * sizes[i + 1]),
                [prev],
            )
        prev = sim.add("head", "e0", GTX_1080TI.compute_time(NET.head_flops), [prev])
    total = sim.run()
    assert total == pytest.approx(t_pre * n_tasks, rel=1e-12)
