"""Property tests on the scheduling core + config registry invariants."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    GTX_1080TI,
    Link,
    plan_halp,
    simulate_halp,
    simulate_modnn,
    standalone_time,
    vgg16_geom,
)
from repro.parallel.pipeline import bubble_fraction

NET = vgg16_geom()


@given(st.sampled_from([1e9, 5e9, 20e9, 60e9, 100e9]))
@settings(max_examples=5, deadline=None)
def test_halp_monotone_in_rate(rate):
    """Faster links never hurt."""
    t_lo = simulate_halp(NET, GTX_1080TI, Link(rate))["total"]
    t_hi = simulate_halp(NET, GTX_1080TI, Link(rate * 2))["total"]
    assert t_hi <= t_lo + 1e-12


@given(st.integers(1, 6))
@settings(max_examples=6, deadline=None)
def test_halp_multitask_scales_sublinearly(k):
    """K tasks on K pairs + shared host finish in << K x single-task time."""
    link = Link(40e9)
    t1 = simulate_halp(NET, GTX_1080TI, link, n_tasks=1)["total"]
    tk = simulate_halp(NET, GTX_1080TI, link, n_tasks=k)["total"]
    assert tk >= t1 - 1e-12
    assert tk <= k * t1  # far better than sequential


@given(st.integers(2, 12))
@settings(max_examples=8, deadline=None)
def test_modnn_more_workers_less_compute_time(n):
    """At infinite rate, MoDNN approaches the 1/n compute bound."""
    t = simulate_modnn(NET, GTX_1080TI, Link(1e15), n)["total"]
    t_pre = standalone_time(NET, GTX_1080TI)
    assert t < t_pre
    assert t > t_pre / n * 0.9  # cannot beat perfect parallelism


@given(st.integers(2, 10))
@settings(max_examples=8, deadline=None)
def test_overlap_zone_width_covers(w):
    """Any overlap width >= 2 yields a valid plan with no secondary exchange
    (the plan constructor asserts it); message bytes decrease in w for e0->ek
    is not guaranteed, but plans must stay consistent."""
    plan = plan_halp(NET, overlap_rows=w)
    sizes = NET.sizes()
    for i, part in enumerate(plan.parts):
        assert part.out["e1"].rows + part.out["e0"].rows + part.out["e2"].rows == sizes[i + 1]


def test_bubble_fraction():
    assert bubble_fraction(2, 1) == pytest.approx(0.5)
    assert bubble_fraction(2, 14) == pytest.approx(1 / 15)
    assert bubble_fraction(8, 56) == pytest.approx(7 / 63)


def test_registry_cells_total_40():
    """The assigned pool: 10 archs x 4 shapes = 40 cells (+ vgg16 extra, and
    swin-b-384, a served configuration with no assigned cells)."""
    from repro.configs import get, list_archs

    assert get("swin-b-384").cells == {}
    assigned = [a for a in list_archs() if a != "vgg16" and get(a).cells]
    assert len(assigned) == 10
    total = sum(len(get(a).cells) for a in assigned)
    assert total == 40
    # every skip is recorded with a reason
    for a in assigned:
        for c in get(a).cells.values():
            if c.skip:
                assert "sub-quadratic" in c.skip


def test_dryrun_artifacts_have_corrected_costs():
    """All ok dry-run records carry the while-trip-corrected hlo_cost."""
    import json
    from pathlib import Path

    results = Path(__file__).resolve().parents[1] / "benchmarks" / "dryrun_results"
    if not results.exists():
        pytest.skip("dry-run not executed")
    n = 0
    for f in results.glob("*__pod16x16.json"):
        rec = json.loads(f.read_text())
        if rec["status"] == "ok":
            assert "hlo_cost" in rec, f.name
            assert rec["hlo_cost"]["flops"] > 0, f.name
            n += 1
    assert n >= 36
