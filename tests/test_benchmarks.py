"""Benchmark-level reproduction assertions: our numbers vs. the paper's."""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import paper_tables


def test_table2_reproduction_quality():
    """HALP throughput within 8% of the paper at every (platform, rate)."""
    out = paper_tables.table2_throughput()
    for (plat, rate), (ours, paper) in out.items():
        assert abs(ours - paper) / paper < 0.08, (plat, rate, ours, paper)


def test_fig6_speedup_band():
    """Single-task x-speedup covers the paper's claim (1.7-2.0x or better)."""
    out = paper_tables.fig6_single_task()
    for (plat, rate), (speedup, rho) in out.items():
        assert speedup >= 1.7, (plat, rate, speedup)
        assert 0 < rho < 1


def test_fig7_multi_task_band():
    """4-task average-delay speedup in/above the paper's 1.67-1.81x band."""
    out = paper_tables.fig7_multi_task()
    for (plat, rate), speedup in out.items():
        assert 1.55 <= speedup <= 2.3, (plat, rate, speedup)


def test_table3_reproduction_quality():
    """Reliability within 2e-3 of the paper at the paper-implied constants."""
    out = paper_tables.table3_reliability()
    for key, (ours, paper) in out.items():
        assert abs(ours - paper) < 2e-3, (key, ours, paper)


def test_table4_optimizer_beats_equal_split():
    """The heterogeneous-cluster optimizer must clearly beat the naive equal
    split (acceptance criterion of the N-way refactor)."""
    out = paper_tables.table4_heterogeneous_optimizer()
    assert out["optimized"] < 0.75 * out["equal"]
    assert out["gain"] > 0.3


def test_hetero_sweep_monotone_gain():
    """Optimizer gain grows with cluster asymmetry; N-way scaling helps."""
    from benchmarks import hetero_sweep

    pairs = hetero_sweep.sweep_heterogeneous_pairs()
    gains = [v["gain"] for v in pairs.values()]
    assert all(b >= a - 0.02 for a, b in zip(gains, gains[1:])), gains
    nway = hetero_sweep.sweep_nway_scaling()
    assert nway[3]["speedup"] > nway[2]["speedup"]


def test_replan_sweep_acceptance():
    """The cached adaptive planner must strictly beat the static nominal-rate
    plan on a time-variant trace (reliability at the 133.3 ms deadline and
    mean makespan), keep the steady-state cache hit rate >= 90%, and every
    replanned plan must execute losslessly via run_plan."""
    from benchmarks import replan_sweep

    out = replan_sweep.run_sweep(include_always=False, max_verify_plans=3)
    static, cached = out["static"], out["cached"]
    assert cached["mean_makespan"] < static["mean_makespan"]
    assert cached["mean_reliability"] > static["mean_reliability"]
    assert cached["min_reliability"] > static["min_reliability"]
    assert cached["steady_state_hit_rate"] >= 0.90
    # the cache amortises: an order of magnitude fewer optimizer calls than
    # the always-replan policy would need (one per epoch)
    assert cached["optimizer_calls"] <= out["n_epochs"] // 5
    assert out["plans_verified_lossless"] == 3


def test_straggler_sweep_acceptance():
    """Joint compute+link adaptation must beat the link-only controller by a
    pinned margin on mean makespan under a straggling secondary (with every
    joint-controller plan verified lossless via run_plan), and must serve
    plans *identical* to the link-only controller when compute never drifts
    (the nominal-anchored compute bands make adaptivity free until a
    straggler appears)."""
    from benchmarks import straggler_sweep

    out = straggler_sweep.run_sweep(n_epochs=40, max_verify_plans=3)
    link_only, joint = out["link_only"], out["joint"]
    # the pinned straggler margin (measured ~21% at 40 epochs, ~28% at 140)
    assert out["joint_vs_link_only_gain"] >= 0.10, out["joint_vs_link_only_gain"]
    assert joint["mean_makespan"] < link_only["mean_makespan"]
    assert joint["max_makespan"] < link_only["max_makespan"]
    assert joint["mean_reliability"] >= link_only["mean_reliability"]
    assert joint["min_reliability"] >= link_only["min_reliability"]
    # compute-blind control is no better than no control here: the channel
    # barely moves the makespan, the straggler dominates it
    assert link_only["mean_makespan"] > 0.95 * out["static"]["mean_makespan"]
    # equality regression: no compute drift -> same plans, same makespans
    assert out["nodrift_plans_equal"] is True
    assert out["nodrift_makespans_equal"] is True
    a_replans, b_replans = out["nodrift_replans"]
    assert a_replans == b_replans  # same link-bucket switches, nothing more
    assert out["plans_verified_lossless"] == 3


def test_spatial_calibration_acceptance():
    """Measured-kernel schedule composition must show the fused kernel's halo
    overlap winning over the unfused exchange-then-compute schedule, the
    capacity-weighted split winning over the equal split on the skewed mesh,
    and the measured (es, flops, elapsed) samples -- round-tripped through
    ComputeRateEstimator -- must pull the DES prediction error far below the
    nominal-rate prediction."""
    from benchmarks import spatial_calibration

    out = spatial_calibration.run_all(smoke=True, out_path=None)
    # the result names its device; Pallas compiles only on a TPU
    assert out["device"]["platform"] == jax.devices()[0].platform
    assert out["pallas_interpret"] == (out["device"]["platform"] != "tpu")
    # fused hides the halo latency behind interior compute: strictly faster
    assert out["fused_speedup"] >= 1.02, out["fused_speedup"]
    # weighted split keeps the slow shard from straggling (caps 1.0..0.35)
    assert out["weighted_speedup"] >= 1.2, out["weighted_speedup"]
    assert sum(out["weighted_heights"]) == sum(out["equal_heights"])
    assert max(out["weighted_heights"]) > max(out["equal_heights"])
    # every conv layer was actually executed and timed on both engines
    convs = [L for L in out["layers"] if L["kind"] != "pool"]
    assert convs and all(L["lax_s"] > 0 and L["pallas_s"] > 0 for L in convs)
    # calibration: measured samples through ComputeRateEstimator must beat
    # the (deliberately wrong) nominal rates by a wide margin
    assert out["err_calibrated"] < 0.5 * out["err_nominal"], (
        out["err_calibrated"], out["err_nominal"])
    assert out["err_calibrated"] < 0.35, out["err_calibrated"]


def test_multitask_placement_acceptance():
    """Per-task heterogeneous placement must strictly beat the paper's
    shared-plan deployment on the same shared-contention DES -- mean per-task
    delay AND batch makespan -- with every plan of both deployments verified
    lossless via run_plan (acceptance criteria of the placement engine)."""
    from benchmarks import multitask_placement

    out = multitask_placement.run_comparison(swap_rounds=2, optimize_final=False)
    shared, per_task = out["shared"], out["per_task"]
    assert per_task["avg_delay"] < shared["avg_delay"]
    assert per_task["makespan"] < shared["makespan"]
    # the heterogeneous pool is skewed enough that capacity-aware grouping
    # alone buys a large margin; pin a conservative floor on it
    assert out["gain_avg"] > 0.25, out["gain_avg"]
    # 4 per-task plans + 4 shared-baseline plans, all bit-compatible
    assert out["plans_verified_lossless"] == 8


def test_planner_speed_acceptance():
    """The batched planning engine must return plans *equal* to the scalar
    path in every scenario (shared search loop, bit-identical pricing) at a
    >= 5x median speedup floor.  Full runs track the >= 10x single-task
    optimize target in BENCH_planner.json; the smoke floor absorbs CI noise."""
    from benchmarks import planner_speed

    out = planner_speed.run_all(smoke=True, out_path=None)
    for name, sc in out["scenarios"].items():
        assert sc["plans_equal"], f"{name}: engines returned different plans"
        assert sc["speedup"] >= 5.0, (name, sc["speedup"])


def test_serve_sweep_acceptance():
    """The serving pipeline under a flash crowd: admission shedding must keep
    every class's deadline-met fraction -- premium above all -- at or above
    the accept-everything baseline, shed a real fraction during the burst,
    and the DES latency table driving admission must be positive and
    non-decreasing in batch width."""
    from benchmarks import serve_sweep

    out = serve_sweep.run_sweep(smoke=True)
    lat = out["lat_table_des"]
    assert all(v > 0 for v in lat)
    assert all(b >= a for a, b in zip(lat, lat[1:])), lat
    # controller's plan-aware curve prices the same cluster: same ballpark
    ratio = out["lat_table_controller"][0] / lat[0]
    assert 0.5 < ratio < 2.0, ratio
    fc = out["processes"]["flash_crowd"]
    assert out["flash_premium_met_shed"] >= out["flash_premium_met_noshed"]
    for cls in ("premium", "standard", "bulk"):
        assert (
            fc["shed"]["classes"][cls]["deadline_met_frac"]
            >= fc["noshed"]["classes"][cls]["deadline_met_frac"]
        ), cls
    assert fc["shed"]["overall"]["shed_rate"] > 0.05
    assert fc["noshed"]["overall"]["shed_rate"] == 0.0
    # off-burst load is comfortable: steady Poisson meets ~everything
    po = out["processes"]["poisson"]
    assert po["shed"]["overall"]["deadline_met_frac"] > 0.99


def test_serve_bench_artifact_floors():
    """The committed full-run artifact must cover >= 10^6 simulated requests
    across the three arrival processes and carry the tail/attainment/shed
    fields per process x policy (the PR's acceptance floor)."""
    import json

    path = Path(__file__).resolve().parents[1] / "BENCH_serve.json"
    if not path.exists():
        pytest.skip("BENCH_serve.json not committed yet")
    out = json.loads(path.read_text())
    assert out["n_total"] >= 1_000_000, out["n_total"]
    assert set(out["processes"]) == {"poisson", "diurnal", "flash_crowd"}
    for rec in out["processes"].values():
        for policy in ("shed", "noshed"):
            o = rec[policy]["overall"]
            for k in ("p99_latency_s", "p999_latency_s", "deadline_met_frac",
                      "shed_rate", "completed"):
                assert k in o, (policy, k)
            assert o["p999_latency_s"] >= o["p99_latency_s"] >= 0.0
            assert set(rec[policy]["classes"]) == {"premium", "standard", "bulk"}
    assert out["flash_premium_met_shed"] >= out["flash_premium_met_noshed"]


def test_planstore_bench_acceptance():
    """Warm restart against a populated PlanStore must serve the whole drift
    trace with ZERO optimizer calls and bit-identical plans/makespans to the
    cold run, and a changed optimizer config must force re-optimisation (the
    tentpole acceptance criteria of the persistent plan store)."""
    from benchmarks import planstore_bench

    out = planstore_bench.run_all(smoke=True, out_path=None)
    assert out["warm_optimizer_calls"] == 0
    assert out["plans_bit_identical"] is True
    assert out["makespans_bit_identical"] is True
    assert out["warm"]["store_hits"] == out["cold"]["optimizer_calls"]
    assert out["reconfigured_reoptimized"] is True
    assert out["reconfigured"]["store_hits"] == 0  # never serves a stale plan
    # the restart speedup is the point: store read vs full optimisation
    assert out["warm_first_plan_speedup"] >= 5.0, out["warm_first_plan_speedup"]
    # drift really exercised the lattice (several operating points visited)
    assert out["distinct_operating_points"] >= 5


def test_planstore_bench_artifact_floors():
    """The committed full-run artifact must carry the warm-restart claims at
    full trace length (the PR's acceptance floor)."""
    import json

    path = Path(__file__).resolve().parents[1] / "BENCH_planstore.json"
    if not path.exists():
        pytest.skip("BENCH_planstore.json not committed yet")
    out = json.loads(path.read_text())
    assert out["n_epochs"] >= 100
    assert out["warm_optimizer_calls"] == 0
    assert out["plans_bit_identical"] is True
    assert out["makespans_bit_identical"] is True
    assert out["reconfigured_reoptimized"] is True
    assert out["warm_first_plan_speedup"] >= 10.0
    assert out["cold"]["optimizer_calls"] >= 20  # real lattice coverage
    assert out["warm"]["store_hits"] == out["cold"]["optimizer_calls"]
    assert out["warm"]["store_entries"] == out["cold"]["store_entries"]


def test_scheme_sweep_acceptance():
    """The joint per-stage scheme search must never lose to halo-only
    planning on any grid cell (it is seeded at the halo-only optimum), must
    cut the makespan by >= 10% on at least one cell (the attention model,
    where halo partitioning cannot apply and head splits can), and every
    cell must carry per-stage comm-byte accounting for both plans."""
    from benchmarks import scheme_sweep

    out = scheme_sweep.run_all(smoke=True, out_path=None)
    assert set(out["cells"]) == {
        "vgg16/sym", "vgg16/skew", "vit_l16/sym", "vit_l16/skew"
    }
    for key, cell in out["cells"].items():
        assert cell["reduction"] >= -1e-12, (key, cell["reduction"])
        n_stages = out["nets"][key.split("/")[0]]["n_stages"]
        for rec in (cell["halo_only"], cell["searched"]):
            bytes_per_stage = rec["comm_bytes_per_stage"]
            assert len(bytes_per_stage) == n_stages
            assert all(b >= 0 for b in bytes_per_stage)
        assert cell["searched"]["makespan"] <= cell["halo_only"]["makespan"]
    assert out["max_reduction"] >= 0.10, out["max_reduction"]
    # the attention model's win comes from head splits, not ratio tweaks
    for topo in ("sym", "skew"):
        searched = out["cells"][f"vit_l16/{topo}"]["searched"]["assignment"]
        assert "head_sequence" in searched, searched


def test_scheme_bench_artifact_floors():
    """The committed full-run artifact must cover the full-size nets and
    carry the tentpole's acceptance numbers (no cell regresses, >= 10%
    reduction somewhere)."""
    import json

    path = Path(__file__).resolve().parents[1] / "BENCH_schemes.json"
    if not path.exists():
        pytest.skip("BENCH_schemes.json not committed yet")
    out = json.loads(path.read_text())
    assert out["smoke"] is False
    assert out["nets"]["vgg16"]["in_rows"] == 224
    assert out["nets"]["vit_l16"]["in_rows"] == 224
    assert out["nets"]["vit_l16"]["n_layers"] == 1 + 24 * 4  # patch + 24 blocks
    assert set(out["cells"]) == {
        "vgg16/sym", "vgg16/skew", "vit_l16/sym", "vit_l16/skew"
    }
    for key, cell in out["cells"].items():
        assert cell["reduction"] >= -1e-12, (key, cell["reduction"])
        assert cell["halo_only"]["comm_bytes_per_stage"]
        assert cell["searched"]["comm_bytes_per_stage"]
    assert out["max_reduction"] >= 0.10, out["max_reduction"]


def test_roofline_results_complete():
    """Dry-run artifacts exist for all 40 cells x both meshes (ok or recorded
    skip), i.e. deliverables (e)/(g) are materialised."""
    from benchmarks import roofline

    for mesh in ("pod16x16", "pod2x16x16"):
        recs = roofline.load_all(mesh)
        if not recs:
            pytest.skip(f"dry-run not yet executed for {mesh}")
        assert len(recs) == 40, (mesh, len(recs))
        bad = [r for r in recs if r["status"] not in ("ok", "skipped")]
        assert not bad, [(r["arch"], r["cell"], r.get("error", "")[:60]) for r in bad]
        skips = [r for r in recs if r["status"] == "skipped"]
        assert len(skips) == 4  # long_500k x 4 full-attention LMs
