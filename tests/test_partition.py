"""Partitioner tests (paper §III eqs. 5-9 + HALP plan invariants)."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.nets import vgg16_geom
from repro.core.partition import (
    E0,
    E1,
    E2,
    PlanInfeasible,
    Segment,
    _reduce_caps,
    plan_even,
    plan_halp,
    plan_halp_n,
    split_rows,
)


def test_split_rows_covers_exactly():
    segs = split_rows(224, [0.49, 0.02, 0.49])
    assert segs[0].lo == 1 and segs[-1].hi == 224
    for a, b in zip(segs, segs[1:]):
        assert b.lo == a.hi + 1
    assert sum(s.rows for s in segs) == 224


@given(
    total=st.integers(4, 500),
    n=st.integers(2, 8),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=200, deadline=None)
def test_split_rows_property(total, n, seed):
    import random

    rng = random.Random(seed)
    raw = [rng.random() + 0.05 for _ in range(n)]
    ratios = [r / sum(raw) for r in raw]
    segs = split_rows(total, ratios)
    assert segs[0].lo == 1 and segs[-1].hi == total
    assert sum(s.rows for s in segs) == total
    for a, b in zip(segs, segs[1:]):
        assert b.lo == a.hi + 1


def test_halp_plan_vgg16_structure():
    net = vgg16_geom()
    plan = plan_halp(net, overlap_rows=4)
    sizes = net.sizes()
    for i, part in enumerate(plan.parts):
        o = sizes[i + 1]
        # segments tile 1..O in (e1, e0, e2) order
        assert part.out[E1].lo == 1
        assert part.out[E2].hi == o
        assert part.out[E0].lo == part.out[E1].hi + 1
        assert part.out[E2].lo == part.out[E0].hi + 1
        # the host zone is thin (the paper's "overlapping zone is only 4 rows")
        if net.layers[i].kind == "conv":
            assert part.out[E0].rows <= 6
        # input ranges stay inside the layer input
        for es in (E1, E0, E2):
            seg = part.inp[es]
            assert 1 <= seg.lo <= seg.hi <= sizes[i]


def test_secondaries_never_exchange():
    net = vgg16_geom()
    plan = plan_halp(net, overlap_rows=4)
    for i in range(len(plan.parts) - 1):
        assert not plan.message(i, E1, E2)
        assert not plan.message(i, E2, E1)


def test_pool_layers_need_no_host_message():
    """Paper §IV.A: 'if the next layer is pooling layer, the host does not need
    to send the output of the current CL to secondary ESs'."""
    net = vgg16_geom()
    plan = plan_halp(net, overlap_rows=4)
    for i, g in enumerate(net.layers[:-1]):
        if net.layers[i + 1].kind == "pool":
            assert plan.message_bytes(i, E0, E1) == 0.0
            assert plan.message_bytes(i, E0, E2) == 0.0


def test_paper_eq10_init_bytes():
    """Eq. (10): the initial slice to each secondary is ~half the image."""
    from repro.core.schedule import _init_bytes

    net = vgg16_geom()
    plan = plan_halp(net, overlap_rows=4)
    for ek in (E1, E2):
        nbytes = _init_bytes(plan, ek)
        # between 45% and 60% of the full 224x224x3 float32 image
        full = 4 * 224 * 224 * 3
        assert 0.45 * full < nbytes < 0.6 * full


def test_message_bytes_match_eq11_form():
    """Our range-algebra message equals the paper's eq. (11) closed form
    4*(IE^{e1}_{gi} - OS^{e0}_{g_{i-1}} + 1)*I*c for host->e1 at conv layers
    whose predecessor partition aligns (the paper's assumed regime)."""
    net = vgg16_geom()
    plan = plan_halp(net, overlap_rows=4)
    sizes = net.sizes()
    checked = 0
    for i in range(1, len(net.layers) - 1):
        g = net.layers[i]
        if g.kind != "conv" or net.layers[i - 1].kind != "conv":
            continue
        ie_e1 = plan.parts[i].inp[E1].hi
        os_e0 = plan.parts[i - 1].out[E0].lo
        if ie_e1 < os_e0:
            continue
        expected = 4 * (ie_e1 - os_e0 + 1) * sizes[i] * g.c_in
        assert plan.message_bytes(i - 1, E0, E1) == expected
        checked += 1
    assert checked >= 4


def test_feasibility_boundary_pinned_vgg16():
    """Regression-pin the jagged feasibility boundary in N on VGG-16, so
    future partitioner changes cannot silently shift it:

    * N=5 and N=8 never trigger auto-reduction -- the strict-isolation plan
      is identical to the default one (their thin layers degrade via
      *idle slots* only: N=5 idles two slots at g16-17, N=8 idles e5 at the
      14x14 block and hands the whole 14-row layers to the host),
    * N=6 is the jagged hole: strict mode raises at the 14-row depth, the
      default auto-reduces to one active secondary there with the host
      absorbing the tail."""
    net = vgg16_geom()
    sizes = net.sizes()

    # --- N=5 / N=8: idle-slot degradation only; auto-reduce is a no-op
    for n in (5, 8):
        secs = tuple(f"e{j}" for j in range(1, n + 1))
        default = plan_halp_n(net, secondaries=secs, overlap_rows=4)
        strict = plan_halp_n(net, secondaries=secs, overlap_rows=4, auto_reduce=False)
        for a, b in zip(default.parts, strict.parts):
            assert a.out == b.out, (n, a.index)

    plan5 = plan_halp_n(net, secondaries=tuple(f"e{j}" for j in range(1, 6)))
    assert plan5.active_secondaries(15) == ("e1", "e2", "e3", "e4", "e5")
    assert plan5.active_secondaries(16) == ("e1", "e3", "e5")  # e2/e4 idle
    assert plan5.active_secondaries(17) == ("e1", "e3", "e5")

    plan8 = plan_halp_n(net, secondaries=tuple(f"e{j}" for j in range(1, 9)))
    for layer in (12, 13, 14, 15):
        assert "e5" not in plan8.active_secondaries(layer)
        assert len(plan8.active_secondaries(layer)) == 7
    # the 14-row layers fit 7 host zones + nothing else: host owns everything
    assert plan8.active_secondaries(16) == ()
    assert sum(plan8.parts[16].out[z].rows for z in plan8.zone_slots) == sizes[17]

    # --- N=6: the hole.  Strict mode raises (the pre-PR boundary) ...
    with pytest.raises(PlanInfeasible, match="exchange rows"):
        plan_halp_n(
            net, secondaries=tuple(f"e{j}" for j in range(1, 7)), auto_reduce=False
        )
    # ... and the default reduces g16-17 to one active secondary + host tail.
    plan6 = plan_halp_n(net, secondaries=tuple(f"e{j}" for j in range(1, 7)))
    acts = [len(plan6.active_secondaries(i)) for i in range(len(plan6.parts))]
    assert acts == [6] * 16 + [1, 1]
    assert plan6.parts[16].out["e1"] == Segment(1, 2)
    assert plan6.parts[16].out["e0#0"] == Segment(3, 14)  # host-owned tail
    for s in ("e2", "e3", "e4", "e5", "e6"):
        assert not plan6.parts[16].out[s]


def test_auto_reduce_terminal_case_raises():
    """_reduce_caps refuses once every candidate layer is down to one active
    secondary -- the 'even N=1 fails' terminal that keeps the loud raise."""
    exc = PlanInfeasible(0, "x", reduce_at=(1, 0))
    caps = [2, 2]
    assert _reduce_caps(caps, exc, [0, 1]) is True and caps == [2, 1]
    assert _reduce_caps(caps, exc, [0, 1]) is True and caps == [1, 1]
    assert _reduce_caps(caps, exc, [0, 1]) is False  # both candidates at 1
    # out-of-range candidates are skipped, not crashed on
    assert _reduce_caps([3], PlanInfeasible(0, "x", reduce_at=(5,)), [0]) is False


def test_plan_even_tiles():
    net = vgg16_geom()
    for n in (2, 3, 4, 8):
        plan = plan_even(net, n)
        for i, part in enumerate(plan.parts):
            o = net.sizes()[i + 1]
            segs = [part.out[w] for w in plan.es_names]
            assert segs[0].lo == 1 and segs[-1].hi == o
            assert sum(s.rows for s in segs) == o
