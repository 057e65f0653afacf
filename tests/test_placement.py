"""Per-task heterogeneous placement: structure, optimizer, controller, and
property-based losslessness of every placement the engine can emit."""
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (
    GTX_1080TI,
    CollabTopology,
    Link,
    PlacementController,
    ReplanConfig,
    ReplanController,
    TaskPlacement,
    place_tasks,
    plan_halp_topology,
    shared_plan_placement,
    simulate_placement,
    simulate_halp,
    vgg16_geom,
)
from repro.core.events import build_multitask_dag
from repro.core.replan import PlanCache
from repro.core.simulator import Sim

NET = vgg16_geom()


def hetero_pool(n: int = 8, slow_links: bool = True) -> CollabTopology:
    scales = (1.0, 1.0, 0.6, 0.6, 0.35, 0.35, 0.2, 0.2, 0.5, 0.9)[:n]
    secs = tuple(f"e{j}" for j in range(1, n + 1))
    platforms = {"e0": GTX_1080TI}
    links = {}
    for s, scale in zip(secs, scales):
        platforms[s] = GTX_1080TI.scaled(scale, f"es x{scale:g}")
        if slow_links and scale < 0.5:
            links[("e0", s)] = Link(10e9)
            links[(s, "e0")] = Link(10e9)
    return CollabTopology(
        host="e0", secondaries=secs, platforms=platforms,
        links=links, default_link=Link(40e9),
    )


# ---------------------------------------------------------------------------
# TaskPlacement structure
# ---------------------------------------------------------------------------


def test_placement_validation():
    pool = hetero_pool(4)
    plan = plan_halp_topology(NET, pool.sub_topology(("e1", "e2")))
    other = plan_halp_topology(NET, pool.sub_topology(("e3", "e4")))
    TaskPlacement(pool=pool, assignments=(("e1", "e2"), ("e3", "e4")), plans=(plan, other))
    with pytest.raises(ValueError, match="more than one task"):
        TaskPlacement(pool=pool, assignments=(("e1", "e2"), ("e1", "e2")), plans=(plan, plan))
    with pytest.raises(ValueError, match="!= assignment"):
        TaskPlacement(pool=pool, assignments=(("e3", "e4"),), plans=(plan,))
    with pytest.raises(ValueError, match="at least one task"):
        TaskPlacement(pool=pool, assignments=(), plans=())


def test_sub_topology_preserves_rates_and_order():
    pool = hetero_pool(6)
    sub = pool.sub_topology(("e5", "e2"))
    assert sub.secondaries == ("e5", "e2")  # caller's order = row order
    assert sub.link_between("e0", "e5").rate_bps == 10e9
    assert sub.link_between("e0", "e2").rate_bps == 40e9
    with pytest.raises(ValueError):
        pool.sub_topology(("e1", "nope"))
    with pytest.raises(ValueError, match="duplicate"):
        pool.sub_topology(("e1", "e1"))


def test_build_multitask_dag_validates():
    pool = hetero_pool(4)
    p1 = plan_halp_topology(NET, pool.sub_topology(("e1", "e2")))
    with pytest.raises(ValueError, match="at least one"):
        build_multitask_dag(Sim(), [], pool)
    foreign = plan_halp_topology(
        NET, CollabTopology.symmetric(GTX_1080TI, Link(40e9), host="h0")
    )
    with pytest.raises(ValueError, match="host"):
        build_multitask_dag(Sim(), [p1, foreign], pool)


def test_multitask_dag_models_contention():
    """Two tasks on the same physical pair must take longer than one (shared
    secondaries + host), but less than twice (pipelining); two tasks on
    disjoint pairs must beat two tasks on one shared pair."""
    pool = hetero_pool(4, slow_links=False)
    pair_a = plan_halp_topology(NET, pool.sub_topology(("e1", "e2")))
    pair_b = plan_halp_topology(NET, pool.sub_topology(("e3", "e4")))

    def makespan(plans):
        sim = Sim()
        heads = build_multitask_dag(sim, plans, pool)
        sim.run()
        return max(sim.finish_of(h) for h in heads)

    one = makespan([pair_a])
    shared = makespan([pair_a, pair_a])
    disjoint = makespan([pair_a, pair_b])
    assert one < shared < 2.0 * one
    assert disjoint < shared


def test_single_task_multitask_dag_matches_simulate_halp():
    """For one task the physical-pool DAG must price exactly like the
    classic per-task-clone DAG (same plan, same rates -- only resource
    names differ)."""
    pool = hetero_pool(2)
    sub = pool.sub_topology(("e1", "e2"))
    plan = plan_halp_topology(NET, sub)
    sim = Sim()
    heads = build_multitask_dag(sim, [plan], pool)
    sim.run()
    ours = max(sim.finish_of(h) for h in heads)
    ref = simulate_halp(NET, topology=sub, plan=plan)["total"]
    assert ours == pytest.approx(ref, rel=1e-12)


# ---------------------------------------------------------------------------
# placement optimizer
# ---------------------------------------------------------------------------


def test_place_tasks_structure_and_quality():
    pool = hetero_pool(8)
    res = place_tasks(NET, pool, 4, optimize_final=False, swap_rounds=2)
    placement = res.placement
    assert placement.n_tasks == 4
    assigned = [s for g in placement.assignments for s in g]
    assert sorted(assigned) == sorted(pool.secondaries)  # partition, no reuse
    assert all(len(g) >= 2 for g in placement.assignments)
    # capacity balance: no task gets both fast ESs
    for g in placement.assignments:
        assert not {"e1", "e2"} <= set(g)
    # the joint score the result reports is reproducible
    sim = simulate_placement(NET, placement)
    assert res.makespan == pytest.approx(sim["total"], rel=1e-12)
    assert res.avg_delay == pytest.approx(sim["avg_delay"], rel=1e-12)


def test_place_tasks_beats_shared_plan_baseline():
    pool = hetero_pool(8)
    shared = simulate_placement(NET, shared_plan_placement(NET, pool, 4))
    res = place_tasks(NET, pool, 4, optimize_final=False, swap_rounds=2)
    assert res.avg_delay < shared["avg_delay"]
    assert res.makespan < shared["total"]


def test_place_tasks_rejects_bad_inputs():
    pool = hetero_pool(4)
    with pytest.raises(ValueError, match="need >="):
        place_tasks(NET, pool, 3)
    with pytest.raises(ValueError, match="objective"):
        place_tasks(NET, pool, 2, objective="latency")
    with pytest.raises(ValueError, match="at least one task"):
        place_tasks(NET, pool, 0)


def test_shared_plan_placement_is_pool_order_equal_split():
    pool = hetero_pool(8)
    placement = shared_plan_placement(NET, pool, 4)
    assert placement.assignments == (
        ("e1", "e2"), ("e3", "e4"), ("e5", "e6"), ("e7", "e8")
    )
    # equal split: first layer segments of both secondaries within one row
    for plan in placement.plans:
        a, b = (plan.parts[0].out[s].rows for s in plan.secondary_slots)
        assert abs(a - b) <= 8  # equal ratios, alignment rounding only


# ---------------------------------------------------------------------------
# controller + serving integration
# ---------------------------------------------------------------------------


def _controller(pool, **options):
    opts = dict(optimize_final=False, swap_rounds=1)
    opts.update(options)
    return PlacementController(
        NET, pool, ReplanConfig(n_tasks=2, max_rounds=2),
        placement_options=opts,
    )


def test_placement_controller_replaces_on_bucket_switch():
    pool = hetero_pool(4)
    ctl = _controller(pool)
    first = ctl.placement_for_epoch()
    assert ctl.optimizer_calls == 1
    # stable channel: cached, no extra optimisation
    again = ctl.placement_for_epoch()
    assert again is first
    assert ctl.optimizer_calls == 1
    # e1's link collapses 40 -> 4 Gbps: bucket switch after hysteresis
    for _ in range(4):
        ctl.observe_transfer("e1", "e0", 1e6, 8.0 * 1e6 / 4e9)
        ctl.observe_transfer("e0", "e1", 1e6, 8.0 * 1e6 / 4e9)
    ctl.placement_for_epoch()
    switched = ctl.placement_for_epoch()
    assert ctl.replans >= 1 and ctl.optimizer_calls == 2
    assert isinstance(switched, TaskPlacement)


def test_placement_controller_replaces_on_compute_straggler():
    """A straggling ES moves its compute bucket: the controller re-places all
    tasks against the degraded platform, and the straggler's assignment load
    shrinks (here: e1, nominally the fastest ES, collapses to 0.15x and the
    new placement no longer leans on it)."""
    pool = hetero_pool(4)
    ctl = _controller(pool)
    first = ctl.placement_for_epoch()
    t_e1 = next(t for t, g in enumerate(first.assignments) if "e1" in g)
    rows_before = sum(
        pt.out["e1"].rows for pt in first.plans[t_e1].parts if "e1" in pt.out
    )
    nom = pool.platform_of("e1").eff_flops
    for _ in range(4):  # past the hysteresis
        ctl.observe_compute("e1", 1e9, 1e9 / (0.15 * nom))
        ctl.placement_for_epoch()
    switched = ctl.placement
    # the gradual EWMA may cross more than one band on its way down
    assert ctl.replans >= 1 and ctl.optimizer_calls >= 2
    assert switched is not first
    # the degraded platform reaches the placement engine...
    est = ctl.estimated_topology()
    # EWMA after 4 samples of 0.15x sits near 0.26x; band rep within a band
    assert est.platform_of("e1").eff_flops < 0.35 * nom
    assert est.platform_of("e2").eff_flops == pool.platform_of("e2").eff_flops
    # ...and the straggler carries fewer rows than it did as the fastest ES
    t_e1b = next(t for t, g in enumerate(switched.assignments) if "e1" in g)
    rows_after = sum(
        pt.out["e1"].rows for pt in switched.plans[t_e1b].parts if "e1" in pt.out
    )
    assert rows_after < rows_before


def test_placement_controller_serving_surface():
    from repro.core.reliability import OffloadChannel
    from repro.runtime.serve import plan_aware_batch_size

    pool = hetero_pool(4)
    ctl = _controller(pool)
    ctl.placement_for_epoch()
    # contention pricing: a batch wrapping onto the same secondaries queues
    lat2, lat4 = ctl.predicted_latency(2), ctl.predicted_latency(4)
    assert lat2 < lat4 < 3.0 * lat2
    ctl.observe_batch_latency(2, lat2 * 1.5)
    assert ctl.predicted_latency(2) > lat2  # calibration folded in
    b = plan_aware_batch_size(
        ctl, deadline_s=4.0 / 30.0,
        channel=OffloadChannel(rate_bps=60e6, sigma_s=5e-3), max_batch=8,
    )
    assert 1 <= b <= 8
    with pytest.raises(TypeError, match="placement_for_epoch"):
        ctl.plan_for_epoch()


def test_controller_kinds_share_one_cache_without_collisions():
    pool = hetero_pool(4)
    cache = PlanCache()
    plan_ctl = ReplanController(NET, pool, ReplanConfig(n_tasks=2, max_rounds=1), cache=cache)
    place_ctl = PlacementController(
        NET, pool, ReplanConfig(n_tasks=2, max_rounds=1), cache=cache,
        placement_options=dict(optimize_final=False, swap_rounds=1),
    )
    plan_ctl.plan_for_epoch()
    place_ctl.placement_for_epoch()
    assert len(cache) == 2  # namespaced by _cache_kind: no overwrite
    assert plan_ctl.optimizer_calls == 1 and place_ctl.optimizer_calls == 1


# ---------------------------------------------------------------------------
# property: every placement executes bit-exact (losslessness)
# ---------------------------------------------------------------------------


@given(
    n_pool=st.integers(4, 6),
    n_tasks=st.integers(1, 2),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=4, deadline=None)
def test_placement_lossless_property(n_pool, n_tasks, seed):
    """Any TaskPlacement over a random feasible heterogeneous pool executes
    bit-exact vs the single-device forward, for every task (run_plan
    reconstructs each layer input strictly from owned rows + plan messages,
    so success proves the message algebra of every per-task plan)."""
    import random

    import jax
    import numpy as np
    from repro.models import vgg
    from repro.spatial import run_plan

    rng = random.Random(seed)
    cfg = vgg.VGGConfig(img_res=64, width_mult=0.25, num_classes=10)
    net = cfg.geom()
    secs = tuple(f"e{j}" for j in range(1, n_pool + 1))
    platforms = {"e0": GTX_1080TI}
    links = {}
    for s in secs:
        platforms[s] = GTX_1080TI.scaled(rng.uniform(0.2, 1.0), f"r{s}")
        rate = rng.choice((10e9, 25e9, 40e9))
        links[("e0", s)] = Link(rate)
        links[(s, "e0")] = Link(rate)
    pool = CollabTopology(
        host="e0", secondaries=secs, platforms=platforms,
        links=links, default_link=Link(40e9),
    )
    res = place_tasks(net, pool, n_tasks, optimize_final=False, swap_rounds=1)

    params = vgg.init(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 64, 64, 3))
    ref = vgg.features(params, cfg, x)
    for plan in res.placement.plans:
        out = run_plan(plan, params["features"], vgg.apply_layer, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )
