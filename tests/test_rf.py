"""Receptive-field arithmetic tests (paper §II, eqs. 1-4, 8-9)."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.rf import (
    LayerGeom,
    RFState,
    conv,
    input_range_exact,
    input_range_paper,
    out_size,
    pool,
    propagate_range,
    rf_chain,
)
from repro.core.nets import vgg16_geom


def test_out_size_eq1():
    assert out_size(224, 3, 1, 1) == 224
    assert out_size(224, 2, 2, 0) == 112
    assert out_size(224, 7, 2, 3) == 112
    assert out_size(224, 11, 4, 2) == 55  # AlexNet conv1


def test_rf_chain_vgg16_block1():
    net = vgg16_geom()
    states = rf_chain(224, net.layers)
    # conv1_1: r=3, j=1 ; conv1_2: r=5, j=1 ; pool1: r=6, j=2
    assert (states[0].rf, states[0].jump) == (3, 1)
    assert (states[1].rf, states[1].jump) == (5, 1)
    assert (states[2].rf, states[2].jump) == (6, 2)
    # output sizes follow eq. (1) through the whole chain
    assert states[-1].out == 7
    # the receptive field of the last conv (conv5_3) in VGG-16 is 196 (literature)
    assert states[-2].rf == 196 and states[-1].rf == 212


def test_input_range_exact_basics():
    # 3x3 s1 p1: output row o needs rows o-1..o+1 clipped
    assert input_range_exact(1, 10, 3, 1, 1, 224) == (1, 11)
    assert input_range_exact(5, 10, 3, 1, 1, 224) == (4, 11)
    assert input_range_exact(220, 224, 3, 1, 1, 224) == (219, 224)
    # 2x2 s2 p0 pool: output row o needs rows 2o-1..2o
    assert input_range_exact(3, 5, 2, 2, 0, 224) == (5, 10)
    # 7x7 s2 p3 stem
    assert input_range_exact(1, 1, 7, 2, 3, 224) == (1, 4)


@given(
    k=st.integers(1, 7),
    s=st.integers(1, 4),
    in_rows=st.integers(8, 64),
    data=st.data(),
)
@settings(max_examples=200, deadline=None)
def test_exact_range_covers_dependency(k, s, in_rows, data):
    """Property: computing a conv restricted to input_range_exact rows gives the
    same values as slicing the full conv output (losslessness, 1-D analogue)."""
    p = data.draw(st.integers(0, k // 2))
    if in_rows + 2 * p < k:
        return
    o = out_size(in_rows, k, s, p)
    o_lo = data.draw(st.integers(1, o))
    o_hi = data.draw(st.integers(o_lo, o))
    x = np.random.RandomState(0).randn(in_rows)
    w = np.ones(k)
    xp = np.pad(x, (p, p))
    full = np.array([xp[(i - 1) * s : (i - 1) * s + k] @ w for i in range(1, o + 1)])
    lo, hi = input_range_exact(o_lo, o_hi, k, s, p, in_rows)
    # re-run the conv on the slice only (with the padding the slice touches)
    pad_lo = p if lo == 1 else 0
    pad_hi = p if hi == in_rows else 0
    xs = np.pad(x[lo - 1 : hi], (pad_lo, pad_hi))
    offset = (o_lo - 1) * s - (lo - 1) - (p - pad_lo)
    part = np.array(
        [xs[offset + (i - o_lo) * s : offset + (i - o_lo) * s + k] @ w for i in range(o_lo, o_hi + 1)]
    )
    np.testing.assert_allclose(part, full[o_lo - 1 : o_hi], atol=1e-12)


@given(
    k=st.integers(1, 5),
    s=st.integers(1, 3),
    in_rows=st.integers(16, 64),
    data=st.data(),
)
@settings(max_examples=200, deadline=None)
def test_paper_range_covers_exact(k, s, in_rows, data):
    """Paper eqs. (8)-(9) vs. exact algebra.

    FINDING (documented in DESIGN.md): the paper's end-row formula (eq. 9,
    ``IE = sigma + (OE+1) j - floor((r-1)/2)``) *under-provisions* input rows
    whenever r > 2j + 1 -- i.e. for any single layer with k > 2s + 1 (5x5/s1
    convs, 7x7/s2 stems, ...).  It is exactly adequate for VGG-16 (k=3, s=1,
    where it coincides with the exact range), which is why the paper's own
    evaluation never trips it.  The start-row formula (eq. 8) is always exact.
    Our framework therefore partitions with the exact interval algebra.
    """
    p = data.draw(st.integers(0, k // 2))
    if in_rows + 2 * p < k:
        return
    g = LayerGeom("g", "conv", k, s, p)
    state = rf_chain(in_rows, [g])[0]
    o = state.out
    o_lo = data.draw(st.integers(1, o))
    o_hi = data.draw(st.integers(o_lo, o))
    e_lo, e_hi = input_range_exact(o_lo, o_hi, k, s, p, in_rows)
    p_lo, p_hi = input_range_paper(o_lo, o_hi, state, in_rows)
    # eq. (8) start row: always covers (and with s=1 exactly matches) the need.
    assert p_lo <= e_lo
    # closed-form deficit of eq. (9) vs. the exact end row (unclipped):
    deficit = (k - 1 - 2 * s) if k % 2 else (k - 2 - 2 * s)
    if deficit <= 0:
        # the paper's regime (VGG-16: k=3, s=1): eq. (9) provisions enough rows.
        assert p_hi >= e_hi
    elif p_hi < in_rows and e_hi < in_rows:
        # paper-bug regime (k > 2s+1): eq. (9) is short by exactly `deficit`.
        assert e_hi - p_hi == deficit


def test_propagate_range_chain():
    net = vgg16_geom()
    # the first output row of the final pool depends on a bounded input window
    ranges = propagate_range(net.layers, 224, len(net.layers) - 1, (1, 1))
    lo, hi = ranges[0]
    assert lo == 1  # clipped at the top
    states = rf_chain(224, net.layers)
    assert hi <= states[-1].rf  # bounded by the cumulative receptive field
    # ranges must be monotone (each level's range maps inside the previous)
    assert len(ranges) == len(net.layers) + 1


def test_cumulative_equals_composed_per_layer():
    """Composing exact per-layer ranges == one-shot propagate (consistency)."""
    net = vgg16_geom()
    li = 8
    ranges = propagate_range(net.layers, 224, li, (3, 20))
    sizes = net.sizes()
    lo, hi = 3, 20
    for i in range(li, -1, -1):
        g = net.layers[i]
        lo, hi = input_range_exact(lo, hi, g.k, g.s, g.p, sizes[i])
    assert (lo, hi) == ranges[0]
