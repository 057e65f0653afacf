"""Spatial-parallelism tests: losslessness of the paper's partitioning in JAX.

Single-device semantic checks run in-process; the SPMD shard_map checks run in
a subprocess with 8 forced host devices (this process keeps the default single
CPU device).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_even, plan_halp
from repro.models import vgg
from repro.spatial import halo_sizes, run_plan

CFG = vgg.VGGConfig(img_res=64, width_mult=0.125, num_classes=10)


@pytest.fixture(scope="module")
def vgg_setup():
    params = vgg.init(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
    ref = vgg.features(params, CFG, x)
    return params, x, ref


def test_halp_plan_lossless(vgg_setup):
    """Paper §II claim: receptive-field partitioning does not change the output.

    The plan executor reconstructs every segment's input strictly from owned
    rows + the plan's messages, so this also proves eqs. (10)-(14) suffice."""
    params, x, ref = vgg_setup
    plan = plan_halp(CFG.geom(), overlap_rows=4)
    out = run_plan(plan, params["features"], vgg.apply_layer, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_even_plan_lossless(vgg_setup, n):
    params, x, ref = vgg_setup
    plan = plan_even(CFG.geom(), n)
    out = run_plan(plan, params["features"], vgg.apply_layer, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "ratios",
    [
        (0.7, 0.3),
        (0.5, 0.3, 0.2),
        (4.0, 2.0, 1.0, 1.0),  # un-normalised capacity weights
    ],
)
def test_weighted_even_plan_lossless(vgg_setup, ratios):
    """Capacity-weighted splits for heterogeneous pods (a pod mixing device
    generations wants row shares proportional to per-device FLOP/s) must stay
    bit-compatible with single-device inference -- the same executable
    backstop that pins the uniform split."""
    params, x, ref = vgg_setup
    plan = plan_even(CFG.geom(), len(ratios), ratios=ratios)
    norm = [r / sum(ratios) for r in ratios]
    # the weighting actually takes effect: first worker's share ~ its ratio
    rows0 = plan.parts[0].out["w0"].rows
    total0 = sum(plan.parts[0].out[es].rows for es in plan.es_names)
    assert abs(rows0 / total0 - norm[0]) < 0.1
    out = run_plan(plan, params["features"], vgg.apply_layer, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_weighted_even_plan_rejects_bad_ratios():
    import pytest as _pytest

    with _pytest.raises(ValueError, match="one ratio per worker"):
        plan_even(CFG.geom(), 3, ratios=(0.5, 0.5))
    with _pytest.raises(ValueError, match="non-negative"):
        plan_even(CFG.geom(), 2, ratios=(1.0, -0.5))


def test_halp_plan_lossless_other_overlaps(vgg_setup):
    params, x, ref = vgg_setup
    for w in (2, 6, 8):
        plan = plan_halp(CFG.geom(), overlap_rows=w)
        out = run_plan(plan, params["features"], vgg.apply_layer, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "secs,ratios",
    [
        (("e1", "e2", "e3"), None),
        (("e1", "e2", "e3"), (0.5, 0.3, 0.2)),
        (("fast", "slow"), (0.72, 0.28)),
        (("a", "b", "c", "d"), (0.4, 0.3, 0.2, 0.1)),
    ],
)
def test_nway_heterogeneous_plan_lossless(vgg_setup, secs, ratios):
    """The executable-losslessness backstop for the N-way refactor: capacity-
    weighted heterogeneous plans (multiple host zones, skewed segments) run
    through the same executor and still match single-device inference."""
    from repro.core.partition import plan_halp_n

    params, x, ref = vgg_setup
    plan = plan_halp_n(CFG.geom(), secondaries=secs, ratios=ratios, overlap_rows=4)
    out = run_plan(plan, params["features"], vgg.apply_layer, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_optimizer_chosen_plan_lossless(vgg_setup):
    """Whatever plan the optimizer proposes must execute losslessly."""
    from repro.core import CollabTopology, GTX_1080TI, Link, optimize_plan

    params, x, ref = vgg_setup
    slow = GTX_1080TI.scaled(0.4, "slow")
    topo = CollabTopology(
        host="e0",
        secondaries=("fast", "slow"),
        platforms={"e0": GTX_1080TI, "fast": GTX_1080TI, "slow": slow},
        default_link=Link(10e9),
    )
    res = optimize_plan(CFG.geom(), topo, overlap_choices=(2, 4), max_rounds=3)
    out = run_plan(res.plan, params["features"], vgg.apply_layer, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_halo_sizes():
    assert halo_sizes(3, 1, 1) == (1, 1)
    assert halo_sizes(1, 1, 0) == (0, 0)
    assert halo_sizes(2, 2, 0) == (0, 0)  # aligned pool: no halo
    assert halo_sizes(7, 2, 3) == (3, 2)
    assert halo_sizes(5, 1, 2) == (2, 2)
    assert halo_sizes(7, 1, 3) == (3, 3)  # ConvNeXt depthwise


def test_exchange_halos_rejects_thin_shards():
    """A halo larger than the shard height would need rows from two shards
    away; ``x[:, -lo:]`` silently truncated to whatever the shard held,
    shipping wrong rows.  It must raise instead -- the geometry check runs
    before any collective, so it is testable without a mesh."""
    from repro.spatial import conv2d_spatial, exchange_halos
    from repro.models.common import conv_params

    x = jnp.zeros((1, 2, 8, 3))  # 2-row shard
    with pytest.raises(ValueError, match="halo exceeds shard height"):
        exchange_halos(x, 3, 0, "sp")  # lo > Hs
    with pytest.raises(ValueError, match="halo exceeds shard height"):
        exchange_halos(x, 0, 3, "sp")  # hi > Hs
    # boundary: a halo of exactly the shard height is legal (whole-shard
    # donation) -- the geometry check must not reject it
    from repro.spatial.halo import _check_halo_fits

    _check_halo_fits(2, 2, 2)  # no raise
    # the overlapped HALP schedule path validates too (its own ppermutes
    # slice x[:, -lo:] the same way): 7x7 conv on a 2-row shard needs lo=hi=3
    params = conv_params(jax.random.PRNGKey(0), 7, 3, 4)
    with pytest.raises(ValueError, match="halo exceeds shard height"):
        conv2d_spatial(x, params, k=7, s=1, p=3, overlap=True)


def test_shard_heights_weighted_split():
    from repro.spatial import shard_heights

    # equal default, exact
    assert shard_heights(64, 4) == (16, 16, 16, 16)
    # capacity-weighted, stride-aligned, sums preserved
    hts = shard_heights(64, 4, ratios=(1.0, 0.55, 0.35, 0.8), align=8)
    assert sum(hts) == 64 and all(h % 8 == 0 for h in hts)
    assert max(hts) > min(hts) >= 8  # genuinely skewed, every shard non-empty
    # heavier ratio never gets fewer rows
    hts2 = shard_heights(60, 3, ratios=(3, 2, 1), align=2)
    assert sum(hts2) == 60 and hts2[0] >= hts2[1] >= hts2[2]
    with pytest.raises(ValueError, match="not divisible"):
        shard_heights(62, 4, align=4)
    with pytest.raises(ValueError, match="at least"):
        shard_heights(16, 5, align=8)  # 2 units cannot feed 5 shards
    with pytest.raises(ValueError, match="one ratio per shard"):
        shard_heights(64, 4, ratios=(1, 2))
    with pytest.raises(ValueError, match="non-negative"):
        shard_heights(64, 2, ratios=(-1, 2))


def test_padded_shard_layout_roundtrip():
    from repro.spatial import merge_padded_shards, to_padded_shards

    hts = (12, 8, 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 5, 3))
    xp = to_padded_shards(x, hts)
    assert xp.shape == (2, 4 * 12, 5, 3)
    # invariant: rows past each shard's valid height are zero
    for j, h in enumerate(hts):
        blk = np.asarray(xp[:, j * 12 : (j + 1) * 12])
        np.testing.assert_array_equal(blk[:, h:], 0.0)
        np.testing.assert_array_equal(blk[:, :h], np.asarray(x[:, sum(hts[:j]) : sum(hts[:j]) + h]))
    np.testing.assert_array_equal(np.asarray(merge_padded_shards(xp, hts)), np.asarray(x))
    with pytest.raises(ValueError, match="sum of shard heights"):
        to_padded_shards(x, (12, 8, 4, 4))
    with pytest.raises(ValueError, match="blocks of"):
        merge_padded_shards(xp[:, :-1], hts)


def test_plan_shard_heights_consumes_weighted_plan():
    """The spatial engine consumes plan_even(ratios=...): the plan's
    first-layer row shares become the deployment's shard heights, re-quantised
    to the net's stride alignment."""
    from repro.spatial import plan_shard_heights, shard_heights, spatial_alignment

    net = CFG.geom()
    align = spatial_alignment(net)
    assert align == 32  # five 2x2 pools
    net3 = vgg.VGGConfig(
        img_res=64, width_mult=0.125, num_classes=10,
        blocks=((2, 64), (2, 128), (3, 256)),
    ).geom()
    align3 = spatial_alignment(net3)
    assert align3 == 8
    plan = plan_even(net3, 4, ratios=(4.0, 2.0, 1.0, 1.0))
    hts = plan_shard_heights(plan, align=align3)
    assert sum(hts) == net3.in_rows and all(h % align3 == 0 for h in hts)
    assert hts[0] >= hts[1] >= hts[2]  # follows the plan's capacity weighting
    # equal plan degenerates to the equal split
    assert plan_shard_heights(plan_even(net3, 4), align=align3) == (16, 16, 16, 16)
    # and the ratios round-trip through the same quantiser
    assert hts == shard_heights(net3.in_rows, 4, ratios=[
        plan.parts[0].out[es].rows for es in plan.es_names], align=align3)


def test_weighted_conv_rejects_bad_heights():
    from repro.models.common import conv_params
    from repro.spatial import conv2d_spatial

    params = conv_params(jax.random.PRNGKey(0), 3, 3, 4)
    x = jnp.zeros((1, 8, 8, 3))
    with pytest.raises(ValueError, match="not all divisible by stride"):
        conv2d_spatial(x, params, k=3, s=2, p=1, heights=(8, 7, 8, 8))
    with pytest.raises(ValueError, match="halo exceeds shard height"):
        conv2d_spatial(x, params, k=7, s=1, p=3, heights=(8, 2, 8, 8))
    with pytest.raises(ValueError, match="positive"):
        conv2d_spatial(x, params, k=3, s=1, p=1, heights=(8, 0, 8, 8))


def test_run_plan_time_observer_attribution(vgg_setup):
    """Zero-config serve-side timing attribution: run_plan emits one
    (es, flops, elapsed) sample per ES whose FLOP counts match the plan's
    exact row algebra, and the samples round-trip through
    ComputeRateEstimator.observe_samples."""
    from repro.core.replan import ComputeRateEstimator

    params, x, ref = vgg_setup
    net = CFG.geom()
    plan = plan_even(net, 3, ratios=(0.5, 0.3, 0.2))
    samples = []
    out = run_plan(plan, params["features"], vgg.apply_layer, x,
                   time_observer=lambda es, fl, dt: samples.append((es, fl, dt)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    assert sorted(es for es, _, _ in samples) == sorted(plan.es_names)
    for es, fl, dt in samples:
        want_fl = sum(
            net.layer_flops(i, plan.parts[i].out[es].rows)
            for i in range(len(net.layers)) if plan.parts[i].out[es]
        )
        assert fl == pytest.approx(want_fl)  # exact row algebra, not a guess
        assert dt > 0
    est = ComputeRateEstimator({es: 1e9 for es in plan.es_names})
    rates = est.observe_samples(samples)
    for es, fl, dt in samples:
        assert rates[es] == pytest.approx(est.rate(es))
        assert est.rate(es) > 0


def _find_jaxpr_with(jaxpr, prim_name):
    """Innermost (sub-)jaxpr whose own eqn list contains ``prim_name``."""
    if any(e.primitive.name == prim_name for e in jaxpr.eqns):
        return jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v if hasattr(v, "eqns") else None)
            if inner is not None and hasattr(inner, "eqns"):
                found = _find_jaxpr_with(inner, prim_name)
                if found is not None:
                    return found
    return None


def _contains_pallas(eqn):
    if eqn.primitive.name == "pallas_call":
        return True
    for v in eqn.params.values():
        inner = getattr(v, "jaxpr", v if hasattr(v, "eqns") else None)
        if inner is not None and hasattr(inner, "eqns"):
            if any(_contains_pallas(e) for e in inner.eqns):
                return True
    return False


def test_weighted_pallas_bottom_halo_overlapped():
    """The fused weighted pallas path must keep the bottom halo OUT of the
    ``pallas_call``: the kernel runs on local rows + the top halo only, and
    the bottom ``ppermute`` is consumed solely by the thin post-kernel fix-up
    conv -- so the scheduler can hide the bottom collective behind the whole
    kernel rather than just its last tiles (ROADMAP direction 5 note).

    Structural pin: in the traced jaxpr, the bottom ppermute's output must
    not be an ancestor of any pallas_call input, yet must still reach the
    function output (through the fix-up).  Plus a numeric losslessness check
    on the same geometry."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.models.common import conv_params
    from repro.models.layers import conv2d
    from repro.spatial import conv2d_spatial

    k, s, p = 5, 1, 1  # lo = 1, hi = 3: halo operands distinguishable by rows
    params = conv_params(jax.random.PRNGKey(0), k, 3, 4)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    heights = (16,)  # min height 16 >= n_fix*s + lo = 4: overlapped path
    fn = shard_map(
        partial(conv2d_spatial, k=k, s=s, p=p, axis_name="sp", overlap=True,
                engine="pallas", interpret=True, heights=heights),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None), P()),
        out_specs=P(None, "sp", None, None),
        check_vma=False,
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8, 3))

    body = _find_jaxpr_with(jax.make_jaxpr(fn)(x, params).jaxpr, "ppermute")
    assert body is not None, "no ppermute in the traced weighted pallas conv"
    pperms = [e for e in body.eqns if e.primitive.name == "ppermute"]
    assert len(pperms) == 2, [e.params for e in pperms]
    bot_pperm = max(pperms, key=lambda e: e.invars[0].aval.shape[1])
    assert bot_pperm.invars[0].aval.shape[1] == 3  # the hi-row donation

    tainted = set(bot_pperm.outvars)
    kernel_seen = False
    for eqn in body.eqns:
        if eqn is bot_pperm:
            continue
        hit = any(hasattr(v, "count") and v in tainted for v in eqn.invars)
        if _contains_pallas(eqn):
            kernel_seen = True
            assert not hit, "pallas_call consumes the bottom ppermute (no overlap)"
        elif hit:
            tainted.update(eqn.outvars)
    assert kernel_seen, "no pallas_call in the traced weighted conv"
    assert any(
        hasattr(v, "count") and v in tainted for v in body.outvars
    ), "bottom halo never reaches the output (fix-up conv missing)"

    # numeric: the overlapped path stays lossless on the same geometry
    # (height pads asymmetrically by the halo sizes: lo above, hi below)
    want = conv2d(x, params, stride=s, padding=[(1, 3), (p, p)])
    got = fn(x, params)[:, : heights[0] // s]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_spmd_halo_exchange_multidevice():
    """Run the shard_map halo-exchange suite on 8 forced host devices."""
    script = os.path.join(os.path.dirname(__file__), "spatial_multidev_impl.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, env=env, timeout=600
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "ALL MULTIDEV SPATIAL CHECKS PASSED" in res.stdout


def test_pallas_engine_fallback_warns():
    """A ``engine="pallas"`` request the fused kernel cannot express
    (``p > k - s``) runs the lax engine -- bit-identical to asking for lax --
    and says so instead of leaving the fallback silent."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.models.common import conv_params
    from repro.spatial import conv2d_spatial

    params = conv_params(jax.random.PRNGKey(0), 3, 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 4))
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))

    def run(engine):
        return shard_map(
            partial(conv2d_spatial, k=3, s=2, p=2, axis_name="sp", engine=engine),
            mesh=mesh, in_specs=(P(None, "sp", None, None), P()),
            out_specs=P(None, "sp", None, None), check_vma=False,
        )(x, params)

    with pytest.warns(UserWarning, match="outside the fused kernel"):
        got = run("pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(run("lax")))
