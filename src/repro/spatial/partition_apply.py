"""Execute a HALP plan segment-by-segment and verify losslessness (paper §II-§IV).

This is the paper's collaboration scheme as *executable dataflow*: each slot's
feature rows are materialised separately, and the input of every layer segment
is reconstructed **strictly** from (a) rows the slot computed itself and (b)
the inter-slot messages the plan prescribes (eqs. 10-14 / exact range algebra).
If the plan's messages were insufficient, reconstruction would fail loudly --
so equality with the single-device reference proves both the receptive-field
partitioning *and* the message algebra.

The executor is topology-agnostic: it walks ``plan.es_names`` generically, so
the same code runs the paper's symmetric ``(e1, e0, e2)`` triple, N-way
capacity-weighted heterogeneous plans (``plan_halp_n`` with skewed ratios and
multiple host zones), and the worker splits of the TPU spatial engine --
including capacity-weighted ``plan_even(..., ratios=...)`` splits for pods
mixing device generations (row shares proportional to per-device FLOP/s).
This is the correctness backstop for every plan the optimizer may propose,
batched or scalar (the batched engine's layouts materialise through the very
same ``plan_from_layout`` path this executor consumes).

Runs on a single device (no shard_map): this is the semantic model. The SPMD
deployment form lives in ``repro.spatial.halo``.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp

from ..core.nets import ConvNetGeom
from ..core.partition import (
    HALPPlan,
    SCHEME_HALO,
    SCHEME_HOST,
    SCHEME_HS,
    SCHEME_NP,
    SchemePlan,
    Segment,
    _split_counts,
)

__all__ = ["run_plan", "segment_forward"]


def _raw_range(o_lo: int, o_hi: int, k: int, s: int, p: int) -> tuple[int, int]:
    """Unclipped input range (may extend into the zero padding)."""
    return (o_lo - 1) * s + 1 - p, (o_hi - 1) * s + k - p


def segment_forward(apply_layer, params, geom, x_rows: jax.Array, seg: Segment,
                    avail: Segment, in_rows: int) -> jax.Array:
    """Compute output rows ``seg`` of one layer given input rows ``avail``
    (a contiguous, 1-indexed slice of the layer input held in ``x_rows``)."""
    raw_lo, raw_hi = _raw_range(seg.lo, seg.hi, geom.k, geom.s, geom.p)
    lo, hi = max(raw_lo, 1), min(raw_hi, in_rows)
    if not (avail.lo <= lo and hi <= avail.hi):
        raise AssertionError(
            f"insufficient rows: need {lo}..{hi}, have {avail.lo}..{avail.hi}"
        )
    sl = x_rows[:, lo - avail.lo : hi - avail.lo + 1]
    pad_top = lo - raw_lo
    pad_bot = raw_hi - hi
    padw = geom.p if geom.kind != "pool" else 0
    if pad_top or pad_bot or padw:
        sl = jnp.pad(sl, ((0, 0), (pad_top, pad_bot), (padw, padw), (0, 0)))
    y = apply_layer(params, geom, sl)
    assert y.shape[1] == seg.rows, (y.shape, seg)
    return y


def run_plan(
    plan: "HALPPlan | SchemePlan",
    layer_params: list,
    apply_layer,
    x: jax.Array,
    time_observer: Callable[[str, float, float], None] | None = None,
    verify: bool = False,
) -> jax.Array:
    """Run the full plan; returns the merged final feature map (host side).

    ``apply_layer(params, geom, x_slice)`` must be the VALID-padding layer
    primitive (``repro.models.vgg.apply_layer`` or compatible).

    ``time_observer(es, flops, elapsed_s)``: zero-config per-ES timing
    attribution.  When set, every ES's segments are executed synchronously
    (``block_until_ready``) and, once per call, the observer receives that
    ES's total FLOP count (exact row algebra via ``net.layer_flops``) and
    measured wall-clock -- the ``(es, flops, elapsed)`` sample
    :meth:`~repro.runtime.serve.BatchingEngine.observe_es_time` /
    :class:`~repro.core.replan.ComputeRateEstimator` expect, with no manual
    bookkeeping in the serving executor.  Timing requires eager per-segment
    execution, so do not wrap the whole ``run_plan`` in ``jax.jit`` when
    observing (jit ``apply_layer`` instead to keep the kernels compiled).

    ``plan`` may also be a :class:`~repro.core.partition.SchemePlan`: each
    segment then executes under its own scheme (halo segments recurse through
    this very function on their sub-plan) and the observer receives samples
    attributed to physical ES names across all segments.

    ``verify=True`` statically verifies the plan
    (:func:`repro.analysis.check_plan` -- coverage, receptive-field halos,
    message legality) before touching any array, raising
    :class:`repro.analysis.AnalysisError` instead of producing a silently
    wrong feature map from a corrupted plan."""
    if verify:
        from ..analysis import check_plan

        check_plan(plan).raise_if_failed("run_plan")
    if isinstance(plan, SchemePlan):
        return _run_scheme_plan(plan, layer_params, apply_layer, x, time_observer)
    net: ConvNetGeom = plan.net
    sizes = net.sizes()
    es_names = plan.es_names

    # initial distribution: each ES receives its eq.-(10) image slice
    avail: dict[str, tuple[Segment, jax.Array]] = {}
    for es in es_names:
        seg = plan.parts[0].inp[es]
        avail[es] = (seg, x[:, seg.lo - 1 : seg.hi])

    flops_acc = {es: 0.0 for es in es_names}
    secs_acc = {es: 0.0 for es in es_names}

    outs: dict[str, jax.Array] = {}
    for i, g in enumerate(net.layers):
        part = plan.parts[i]
        outs = {}
        for es in es_names:
            if not part.out[es]:
                outs[es] = None
                continue
            t0 = time.perf_counter() if time_observer else 0.0
            with jax.named_scope(f"layer{i:02d}/{es}"):
                y = segment_forward(
                    apply_layer, layer_params[i], g, avail[es][1], part.out[es],
                    avail[es][0], sizes[i],
                )
            if time_observer:
                jax.block_until_ready(y)
                secs_acc[es] += time.perf_counter() - t0
                flops_acc[es] += net.layer_flops(i, part.out[es].rows)
            outs[es] = y
        if i + 1 == len(net.layers):
            break
        # message exchange: every ES's next-layer input = own rows + messages
        with jax.named_scope(f"layer{i:02d}/exchange"):
            new_avail = {}
            for dst in es_names:
                pieces: list[tuple[Segment, jax.Array]] = []
                own = part.out[dst]
                if own:
                    pieces.append((own, outs[dst]))
                for src in es_names:
                    seg = plan.message(i, src, dst)
                    if seg:
                        src_seg = part.out[src]
                        sl = outs[src][:, seg.lo - src_seg.lo : seg.hi - src_seg.lo + 1]
                        pieces.append((seg, sl))
                if not pieces:  # ES owns no rows at this depth (tiny feature map)
                    new_avail[dst] = (Segment(1, 0), None)
                    continue
                pieces.sort(key=lambda t: t[0].lo)
                for (a, _), (b, _) in zip(pieces, pieces[1:]):
                    if b.lo != a.hi + 1:
                        raise AssertionError(f"non-contiguous input for {dst} at layer {i}")
                seg_all = Segment(pieces[0][0].lo, pieces[-1][0].hi)
                new_avail[dst] = (seg_all, jnp.concatenate([t[1] for t in pieces], axis=1))
        avail = new_avail

    if time_observer:
        for es in es_names:
            if flops_acc[es] > 0 and secs_acc[es] > 0:
                time_observer(es, flops_acc[es], secs_acc[es])

    # final merge on the host (paper: sub-outputs -> FL input)
    ordered = sorted(es_names, key=lambda es: plan.parts[-1].out[es].lo)
    with jax.named_scope("merge"):
        return jnp.concatenate([outs[es] for es in ordered if plan.parts[-1].out[es]], axis=1)


def _slice_last_axis(params, lo: int, hi: int):
    """Every array leaf's last axis restricted to ``[lo, hi)`` -- the shared
    shard selector for output-channel splits (conv ``w``/``b``) and head-major
    Q/K/V splits (slicing ``[lo*dh, hi*dh)`` picks whole heads)."""
    return jax.tree_util.tree_map(lambda a: a[..., lo:hi], params)


def _bounds(counts: list[int]) -> list[int]:
    out = [0]
    for c in counts:
        out.append(out[-1] + c)
    return out


def _run_scheme_plan(
    plan: SchemePlan,
    layer_params: list,
    apply_layer,
    x: jax.Array,
    time_observer: Callable[[str, float, float], None] | None,
) -> jax.Array:
    """Execute a mixed-scheme plan segment-by-segment (hub model).

    The host holds the full feature map at every segment boundary.  Halo
    segments recurse through :func:`run_plan` on their sub-plan (row algebra
    verified there); hub segments materialise each secondary's shard from
    *exactly* the slice of parameters/input its scheme prescribes -- a
    non-penetrative secondary only ever sees its filter slice, a head/sequence
    secondary its head or token-row range -- and concatenation along the split
    axis reconstructs the layer output, so equality with the single-device
    reference proves the scheme's losslessness the same way the halo
    executor's strict reconstruction does."""
    net: ConvNetGeom = plan.net
    sizes = net.sizes()
    host = plan.host
    all_es = (*plan.secondaries, host)
    flops_acc = {es: 0.0 for es in all_es}
    secs_acc = {es: 0.0 for es in all_es}

    def acc(es: str, fl: float, dt: float) -> None:
        flops_acc[es] += fl
        secs_acc[es] += dt

    def timed(es: str, fl: float, fn):
        if time_observer is None:
            return fn()
        t0 = time.perf_counter()
        y = fn()
        jax.block_until_ready(y)
        acc(es, fl, time.perf_counter() - t0)
        return y

    for seg, hp in zip(plan.segments, plan.halo_plans):
        if seg.scheme == SCHEME_HALO:
            sub_obs = (
                (lambda slot, fl, dt, _hp=hp: acc(_hp.owner_of(slot), fl, dt))
                if time_observer
                else None
            )
            x = run_plan(
                hp,
                layer_params[seg.start : seg.stop + 1],
                apply_layer,
                x,
                time_observer=sub_obs,
            )
            continue
        for off in range(seg.stop - seg.start + 1):
            i = seg.start + off
            g = net.layers[i]
            avail = Segment(1, sizes[i])
            full_out = Segment(1, sizes[i + 1])
            if seg.scheme == SCHEME_HOST:
                x = timed(
                    host,
                    net.layer_flops(i),
                    lambda: segment_forward(
                        apply_layer, layer_params[i], g, x, full_out, avail, sizes[i]
                    ),
                )
                continue
            pieces: list[jax.Array] = []
            if seg.scheme == SCHEME_NP:
                b = _bounds(_split_counts(g.c_out, plan.ratios))
                for j, es in enumerate(plan.secondaries):
                    lo, hi = b[j], b[j + 1]
                    if lo == hi:
                        continue
                    frac = (hi - lo) / g.c_out
                    if g.kind == "conv":
                        # dense filters: full input, a slice of the filters
                        y = timed(
                            es,
                            net.layer_flops(i) * frac,
                            lambda: segment_forward(
                                apply_layer,
                                _slice_last_axis(layer_params[i], lo, hi),
                                g, x, full_out, avail, sizes[i],
                            ),
                        )
                    else:
                        # channel-local (pool/depthwise): slice of the channels
                        p = (
                            _slice_last_axis(layer_params[i], lo, hi)
                            if layer_params[i]
                            else layer_params[i]
                        )
                        y = timed(
                            es,
                            net.layer_flops(i) * frac,
                            lambda: segment_forward(
                                apply_layer, p, g, x[..., lo:hi], full_out,
                                avail, sizes[i],
                            ),
                        )
                    pieces.append(y)
                x = jnp.concatenate(pieces, axis=-1)
            elif seg.scheme == SCHEME_HS:
                if g.kind == "attn":
                    dh = g.c_in // g.heads
                    b = _bounds(_split_counts(g.heads, plan.ratios))
                    for j, es in enumerate(plan.secondaries):
                        lo, hi = b[j] * dh, b[j + 1] * dh
                        if lo == hi:
                            continue
                        frac = (b[j + 1] - b[j]) / g.heads
                        y = timed(
                            es,
                            net.layer_flops(i) * frac,
                            lambda: apply_layer(
                                _slice_last_axis(layer_params[i], lo, hi), g, x
                            ),
                        )
                        pieces.append(y)
                    x = jnp.concatenate(pieces, axis=-1)
                else:
                    b = _bounds(_split_counts(sizes[i + 1], plan.ratios))
                    for j, es in enumerate(plan.secondaries):
                        rows = Segment(b[j] + 1, b[j + 1])
                        if not rows:
                            continue
                        y = timed(
                            es,
                            net.layer_flops(i, rows.rows),
                            lambda: segment_forward(
                                apply_layer, layer_params[i], g, x, rows,
                                avail, sizes[i],
                            ),
                        )
                        pieces.append(y)
                    x = jnp.concatenate(pieces, axis=1)
            else:
                raise AssertionError(f"unknown scheme {seg.scheme!r}")

    if time_observer:
        for es in all_es:
            if flops_acc[es] > 0 and secs_acc[es] > 0:
                time_observer(es, flops_acc[es], secs_acc[es])
    return x
