"""SPMD spatial parallelism: receptive-field-exact halo exchange (TPU form of HALP).

Under ``shard_map`` the image height axis is sharded across a mesh axis.  Each
device computes a conv layer on its own rows after exchanging the thin halo the
receptive field requires (``halo_lo = p`` rows from the neighbour above,
``halo_hi = k - p - s`` rows from below, the exact analogue of the paper's
eqs. 8-9 for an aligned N-way split).

Two execution modes:

* ``overlap=False`` -- exchange, then one VALID conv over the extended slab.
* ``overlap=True``  -- the HALP schedule: the ``ppermute`` for the halos is
  issued first, the *interior* rows (which need no remote data) are convolved
  immediately, and the boundary rows are finished when the halos land.  On TPU
  the XLA latency-hiding scheduler overlaps the collective with the interior
  conv -- communication is hidden behind compute, exactly the paper's
  "seamless collaboration" (see DESIGN.md for the host-ES -> SPMD mapping).

Two compute engines:

* ``engine="lax"``    -- XLA convs (three per layer under ``overlap=True``).
* ``engine="pallas"`` -- the HALP-fused kernel
  (:func:`repro.kernels.halo_conv.halo_conv2d`): ONE ``pallas_call`` whose
  interior row tiles gather straight from the shard while the boundary tiles
  are the only consumers of ``ppermute`` data, so the overlap happens at
  kernel granularity (eqs. 9-15; docs/equations.md#fused-kernel).  Geometries
  the kernel cannot express (``p > k - s``, grouped non-depthwise convs) fall
  back to the bit-compatible ``lax`` path with a warning.

Capacity-weighted shards (``heights=...``): a pod mixing device generations
deploys the *skewed* split the optimizer chose (``plan_even(ratios=...)``)
instead of the equal one.  Per-device blocks stay equal-shaped (shard_map
needs that): shard ``j`` holds ``heights[j]`` valid rows **top-aligned** in a
``max(heights)``-row block, and every row past the valid region MUST be zero
(:func:`to_padded_shards` builds the layout; the spatial ops preserve the
invariant by masking their outputs).  Halo donations then come from each
shard's *valid* edge rows -- the bottom donation is a dynamic slice at
``heights[j] - lo`` -- and edge shards receive zeros (the conv's zero
padding), so per-shard output offsets and edge padding track the skewed
split exactly.
"""
from __future__ import annotations

import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.halo_conv.halo_conv import halo_conv2d

__all__ = [
    "halo_sizes",
    "exchange_halos",
    "conv2d_spatial",
    "max_pool_spatial",
    "shard_heights",
    "plan_shard_heights",
    "spatial_alignment",
    "to_padded_shards",
    "merge_padded_shards",
]


def halo_sizes(k: int, s: int, p: int) -> tuple[int, int]:
    """Rows needed from the neighbour above / below for an aligned shard."""
    lo, hi = p, k - p - s
    if lo < 0 or lo >= k or hi >= k:
        raise ValueError(f"unsupported geometry k={k} s={s} p={p}")
    return lo, max(0, hi)


def _check_halo_fits(hs: int, lo: int, hi: int) -> None:
    """A neighbour can only donate rows it owns: a halo larger than the shard
    height would need rows from *two* shards away.  ``x[:, -lo:]`` silently
    truncates to the ``hs`` available rows in that case -- the receiving
    shard would convolve wrong (shifted) rows -- so fail loudly instead."""
    if lo > hs or hi > hs:
        raise ValueError(
            f"halo exceeds shard height: need lo={lo}/hi={hi} rows from the "
            f"neighbouring shards but each shard holds only {hs} rows; use "
            f"fewer/taller shards (or run this layer unsharded)"
        )


# ---------------------------------------------------------------------------
# capacity-weighted shard layout
# ---------------------------------------------------------------------------


def _norm_ratios(n: int, ratios) -> list[float]:
    if ratios is None:
        return [1.0 / n] * n
    ratios = list(ratios)
    if len(ratios) != n:
        raise ValueError(f"need one ratio per shard, got {len(ratios)} for n={n}")
    total = sum(ratios)
    if total <= 0 or any(r < 0 for r in ratios):
        raise ValueError(f"ratios must be non-negative with a positive sum, got {ratios}")
    return [r / total for r in ratios]


def shard_heights(
    total: int, n: int, ratios: Sequence[float] | None = None, align: int = 1
) -> tuple[int, ...]:
    """Capacity-weighted shard heights: ``n`` positive row counts summing to
    ``total``, each a multiple of ``align`` (the product of the strides the
    deployment steps through, so every later layer keeps per-shard stride
    alignment), shares within one ``align`` unit of the ratio split."""
    from ..core.partition import _min_one_unit, _split_counts

    if total % align:
        raise ValueError(f"total rows {total} not divisible by alignment {align}")
    units = total // align
    if units < n:
        raise ValueError(
            f"cannot give {n} shards at least {align} rows each from {total}"
        )
    counts = _min_one_unit(_split_counts(units, _norm_ratios(n, ratios)), units)
    return tuple(c * align for c in counts)


def spatial_alignment(net) -> int:
    """Product of all layer strides of a :class:`~repro.core.nets.ConvNetGeom`
    -- the ``align`` that keeps weighted shard heights stride-divisible at
    every depth of the network."""
    align = 1
    for g in net.layers:
        align *= g.s
    return align


def plan_shard_heights(plan, align: int = 1) -> tuple[int, ...]:
    """Input-shard heights deploying an N-way ``plan_even(ratios=...)`` plan
    through ``shard_map``: the plan's first-layer row shares (the optimizer's
    capacity weighting), re-quantised to ``align``.  This is how the spatial
    engine *consumes* the planner's weighted split."""
    rows = [plan.parts[0].out[es].rows for es in plan.es_names]
    return shard_heights(plan.net.in_rows, len(rows), ratios=rows, align=align)


def to_padded_shards(x: jax.Array, heights: Sequence[int]) -> jax.Array:
    """Re-lay a global [B, H, ...] tensor (H == sum(heights)) into the padded
    weighted-shard form: [B, n * max(heights), ...] where shard ``j``'s block
    holds its ``heights[j]`` rows top-aligned and zeros below (the invariant
    every weighted spatial op preserves)."""
    heights = tuple(int(h) for h in heights)
    if x.shape[1] != sum(heights):
        raise ValueError(f"rows {x.shape[1]} != sum of shard heights {sum(heights)}")
    hmax = max(heights)
    pads = [(0, 0)] * (x.ndim - 2)
    parts, off = [], 0
    for h in heights:
        parts.append(jnp.pad(x[:, off : off + h], ((0, 0), (0, hmax - h), *pads)))
        off += h
    return jnp.concatenate(parts, axis=1)


def merge_padded_shards(y: jax.Array, heights: Sequence[int]) -> jax.Array:
    """Inverse of :func:`to_padded_shards`: drop each block's padding rows and
    re-concatenate the valid rows (``heights`` are the *output* heights of the
    layer stack, e.g. the input heights divided by the total stride)."""
    heights = tuple(int(h) for h in heights)
    hmax = max(heights)
    if y.shape[1] != hmax * len(heights):
        raise ValueError(
            f"rows {y.shape[1]} != {len(heights)} blocks of {hmax} padded rows"
        )
    return jnp.concatenate(
        [y[:, j * hmax : j * hmax + h] for j, h in enumerate(heights)], axis=1
    )


def _heights_setup(heights, axis_name: str, lo: int, hi: int, s: int):
    """Validate a weighted layout against the mesh + geometry; returns the
    normalised heights, this shard's index, and its (traced) valid height."""
    heights = tuple(int(h) for h in heights)
    if any(h <= 0 for h in heights):
        raise ValueError(f"shard heights must be positive, got {heights}")
    if s > 1 and any(h % s for h in heights):
        raise ValueError(f"shard heights {heights} not all divisible by stride {s}")
    _check_halo_fits(min(heights), lo, hi)
    n = lax.psum(1, axis_name)
    if len(heights) != n:
        raise ValueError(f"got {len(heights)} shard heights for a {n}-way mesh axis")
    idx = lax.axis_index(axis_name)
    hs_j = jnp.asarray(heights, jnp.int32)[idx]
    return heights, idx, hs_j


def _issue_halos_weighted(x, lo, hi, heights, hs_j, axis_name):
    """ppermute the *valid-edge* rows of each weighted shard: the bottom
    donation starts at the dynamic row ``hs_j - lo``.  Non-wrapping perms:
    edge shards receive zeros (the conv's zero padding)."""
    n = len(heights)
    top = bot = None
    if lo:
        donate = lax.dynamic_slice_in_dim(x, hs_j - lo, lo, axis=1)
        top = lax.ppermute(donate, axis_name, [(i, i + 1) for i in range(n - 1)])
    if hi:
        bot = lax.ppermute(x[:, :hi], axis_name, [(i, i - 1) for i in range(1, n)])
    return top, bot


def _weighted_ext(x, top, bot, lo, hi, hs_j):
    """[top_halo; x; bottom_halo] in the weighted layout: the bottom halo is
    spliced at the dynamic row ``lo + hs_j`` (right below the valid region);
    rows between the halo and the block end stay zero."""
    ext = x
    if lo:
        ext = jnp.concatenate([top, ext], axis=1)
    if hi:
        ext = jnp.concatenate([ext, jnp.zeros_like(bot)], axis=1)
        ext = lax.dynamic_update_slice_in_dim(ext, bot, lo + hs_j, axis=1)
    return ext


def _mask_rows(y, o_j):
    """Zero rows past the shard's valid output height (the layout invariant)."""
    keep = (jnp.arange(y.shape[1]) < o_j)[None, :, None, None]
    return jnp.where(keep, y, jnp.zeros((), y.dtype))


# ---------------------------------------------------------------------------
# halo exchange
# ---------------------------------------------------------------------------


def exchange_halos(
    x: jax.Array, lo: int, hi: int, axis_name: str,
    heights: Sequence[int] | None = None,
) -> jax.Array:
    """Return x extended with ``lo`` rows from above and ``hi`` rows from below.

    Edge shards receive zeros (the conv's zero padding).  x: [B, Hs, W, C].
    Raises ``ValueError`` when the shard is too thin to donate the requested
    halo (``lo > Hs`` or ``hi > Hs``) instead of silently truncating.

    With ``heights`` (capacity-weighted layout) the donations come from each
    shard's valid edge rows and the bottom halo lands at the dynamic row
    ``lo + heights[j]`` of the returned buffer (zeros in between)."""
    if heights is not None:
        heights, _idx, hs_j = _heights_setup(heights, axis_name, lo, hi, 1)
        if x.shape[1] != max(heights):
            raise ValueError(
                f"block height {x.shape[1]} != max shard height {max(heights)}"
            )
        top, bot = _issue_halos_weighted(x, lo, hi, heights, hs_j, axis_name)
        return _weighted_ext(x, top, bot, lo, hi, hs_j)
    _check_halo_fits(x.shape[1], lo, hi)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    parts = [x]
    if lo:
        down = [(i, (i + 1) % n) for i in range(n)]  # my bottom rows -> next shard
        top = lax.ppermute(x[:, -lo:], axis_name, down)
        top = jnp.where(idx == 0, jnp.zeros_like(top), top)
        parts.insert(0, top)
    if hi:
        up = [(i, (i - 1) % n) for i in range(n)]  # my top rows -> previous shard
        bot = lax.ppermute(x[:, :hi], axis_name, up)
        bot = jnp.where(idx == n - 1, jnp.zeros_like(bot), bot)
        parts.append(bot)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else x


def _conv_valid(x, p, s, groups=1):
    y = lax.conv_general_dilated(
        x, p["w"], (s, s), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    if "b" in p:
        y = y + p["b"]
    return y


def _pallas_supported(
    k: int, s: int, p: int, groups: int, c: int, wts, w: int | None = None
) -> bool:
    """The single source of truth for fused-path eligibility: geometries the
    fused kernel expresses are exact halos (p <= k - s), groups either trivial
    or depthwise, and -- given the shard width ``w`` -- a positive output
    width (``w + 2p >= k``; narrower maps make ``(w + 2p - k) // s + 1 <= 0``
    and the kernel's reshape blows up mid-trace).  Agreement with what
    ``halo_conv2d`` actually traces is pinned by
    ``repro.analysis.kernel_check``."""
    if k - p - s < 0:
        return False
    if w is not None and w + 2 * p < k:
        return False
    return groups == 1 or (groups == c == wts.shape[-1] and wts.shape[2] == 1)


def _use_pallas(engine: str, k, s, p, groups, c, wts, w) -> bool:
    """Whether ``engine`` selects the fused kernel for this geometry; a
    ``"pallas"`` request the kernel cannot express runs the lax engine and
    says so."""
    if engine != "pallas":
        return False
    if _pallas_supported(k, s, p, groups, c, wts, w):
        return True
    warnings.warn(
        f"conv2d_spatial(engine='pallas'): k={k} s={s} p={p} groups={groups} "
        f"width={w} is outside the fused kernel; running the lax engine",
        stacklevel=3,
    )
    return False


def conv2d_spatial(
    x: jax.Array,
    params,
    k: int,
    s: int = 1,
    p: int = 0,
    axis_name: str = "sp",
    overlap: bool = True,
    groups: int = 1,
    engine: str = "lax",
    interpret: bool = False,
    heights: Sequence[int] | None = None,
) -> jax.Array:
    """Spatially-sharded conv (height axis sharded over ``axis_name``).

    Requires the shard height to be a multiple of ``s``.  Width uses ordinary
    SAME semantics via explicit padding.

    ``engine="pallas"`` fuses boundary-row packing + conv into one
    ``pallas_call`` (interior tiles never touch the halos -- the HALP overlap
    at kernel granularity); unsupported geometries fall back to ``lax`` with
    a warning.
    NOTE: ``pallas_call`` has no shard_map replication rule, so the enclosing
    ``jax.shard_map`` must pass ``check_vma=False`` when this engine is selected.
    ``interpret=True`` runs the kernel in interpreter mode (CI / CPU).
    ``heights`` switches to the capacity-weighted padded layout (see module
    docstring)."""
    if engine not in ("lax", "pallas"):
        raise ValueError(f"unknown engine {engine!r}; use 'lax' or 'pallas'")
    if heights is not None:
        return _conv2d_spatial_weighted(
            x, params, k, s, p, axis_name, overlap, groups, engine, interpret, heights
        )
    b, hs, w, c = x.shape
    if hs % s:
        raise ValueError(f"shard rows {hs} not divisible by stride {s}")
    lo, hi = halo_sizes(k, s, p)

    if _use_pallas(engine, k, s, p, groups, c, params["w"], w):
        # --- fused path: ppermute halos, then ONE kernel whose boundary tiles
        # are the only consumers of the remote rows (eqs. 9-15 fused).
        _check_halo_fits(hs, lo, hi)
        n = lax.psum(1, axis_name)
        top = (
            lax.ppermute(x[:, -lo:], axis_name, [(i, i + 1) for i in range(n - 1)])
            if lo else None
        )
        bot = (
            lax.ppermute(x[:, :hi], axis_name, [(i, i - 1) for i in range(1, n)])
            if hi else None
        )
        return halo_conv2d(
            x, top, bot, params["w"], params.get("b"),
            stride=s, padding=p, groups=groups, interpret=interpret,
        )

    if p:  # width padding (the height padding is the edge shards' zero halos)
        x = jnp.pad(x, ((0, 0), (0, 0), (p, p), (0, 0)))

    if not overlap or (lo == 0 and hi == 0):
        ext = exchange_halos(x, lo, hi, axis_name)
        y = _conv_valid(ext, params, s, groups)
        return y[:, : hs // s]

    # --- HALP schedule: issue halos first, compute interior, then boundaries.
    # (x is already width-padded, so the halos carry the width padding too.)
    _check_halo_fits(hs, lo, hi)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    top_halo = bot_halo = None
    if lo:
        top_halo = lax.ppermute(
            x[:, -lo:], axis_name, [(i, (i + 1) % n) for i in range(n)]
        )
        top_halo = jnp.where(idx == 0, jnp.zeros_like(top_halo), top_halo)
    if hi:
        bot_halo = lax.ppermute(
            x[:, :hi], axis_name, [(i, (i - 1) % n) for i in range(n)]
        )
        bot_halo = jnp.where(idx == n - 1, jnp.zeros_like(bot_halo), bot_halo)

    # Within-shard output row t (0-indexed) reads extended rows
    # [t*s - lo, t*s - lo + k); interior rows touch no halo.
    nrows = hs // s
    t_lo = -(-lo // s)  # ceil(lo / s)
    t_hi = (hs + lo - k) // s
    if t_hi < t_lo:  # shard too thin for an interior: plain exchanged conv
        parts = [q for q in (top_halo, x, bot_halo) if q is not None]
        ext = jnp.concatenate(parts, axis=1) if len(parts) > 1 else x
        return _conv_valid(ext, params, s, groups)[:, :nrows]

    pieces = []
    if t_lo > 0:  # top boundary rows 0..t_lo-1 finish once the top halo lands
        slab = jnp.concatenate([top_halo, x[:, : (t_lo - 1) * s - lo + k]], axis=1)
        pieces.append(_conv_valid(slab, params, s, groups)[:, :t_lo])
    pieces.append(
        _conv_valid(x[:, t_lo * s - lo : t_hi * s - lo + k], params, s, groups)
    )
    if t_hi + 1 < nrows:  # bottom boundary rows
        slab = x[:, (t_hi + 1) * s - lo :]
        if bot_halo is not None:
            slab = jnp.concatenate([slab, bot_halo], axis=1)
        pieces.append(_conv_valid(slab, params, s, groups)[:, : nrows - t_hi - 1])
    return jnp.concatenate(pieces, axis=1) if len(pieces) > 1 else pieces[0]


def _conv2d_spatial_weighted(
    x, params, k, s, p, axis_name, overlap, groups, engine, interpret, heights
):
    """Capacity-weighted conv over padded blocks (see module docstring)."""
    b, hmax, w, c = x.shape
    lo, hi = halo_sizes(k, s, p)
    heights, _idx, hs_j = _heights_setup(heights, axis_name, lo, hi, s)
    if hmax != max(heights):
        raise ValueError(f"block height {hmax} != max shard height {max(heights)}")
    o_j = hs_j // s
    o_max = hmax // s
    wts = params["w"]

    # halos are issued from the *unpadded* shard, before anything else, so
    # both engines can overlap them with interior compute
    top, bot = _issue_halos_weighted(x, lo, hi, heights, hs_j, axis_name)

    if _use_pallas(engine, k, s, p, groups, c, wts, w):
        pad_rows = hi + (-(hmax + hi)) % s
        x_ext = (
            jnp.concatenate([x, jnp.zeros((b, pad_rows, w, c), x.dtype)], axis=1)
            if pad_rows else x
        )
        zero_bot = jnp.zeros((b, hi, w, c), x.dtype) if hi else None
        n_fix = -(-hi // s)  # valid output rows whose window crosses the bottom edge
        if hi and min(heights) >= n_fix * s + lo:
            # Overlapped bottom halo: the kernel never consumes the bottom
            # ppermute (its bottom operand is zeros and the rows below the
            # valid region are the layout's zeros), so the scheduler can hide
            # that collective behind the *whole* kernel, not just its last
            # tiles.  The last n_fix valid rows -- the only ones whose window
            # crosses the shard's bottom edge -- are then recomputed by a thin
            # fix-up conv, the sole consumer of the bottom halo.  The top halo
            # stays a kernel operand (only tile 0 reads it).
            y = halo_conv2d(
                x_ext, top, zero_bot, wts, params.get("b"),
                stride=s, padding=p, groups=groups, interpret=interpret,
            )
            slab = lax.dynamic_slice_in_dim(
                x, hs_j - n_fix * s - lo, n_fix * s + lo, axis=1
            )
            slab = jnp.concatenate([slab, bot], axis=1)
            if p:
                slab = jnp.pad(slab, ((0, 0), (0, 0), (p, p), (0, 0)))
            y_fix = _conv_valid(slab, params, s, groups)
            y = lax.dynamic_update_slice_in_dim(
                y[:, :o_max], y_fix, o_j - n_fix, axis=1
            )
            return _mask_rows(y, o_j)
        # Shards too thin to source the fix-up slab locally (or hi == 0):
        # embed the bottom halo at its dynamic row pre-kernel (the splice
        # serialises the bottom collective before the kernel, but only rows
        # shorter than n_fix*s + lo ever take this path).
        if hi:
            x_ext = lax.dynamic_update_slice_in_dim(x_ext, bot, hs_j, axis=1)
        y = halo_conv2d(
            x_ext, top, zero_bot, wts, params.get("b"),
            stride=s, padding=p, groups=groups, interpret=interpret,
        )
        return _mask_rows(y[:, :o_max], o_j)

    def wpad(a):
        return jnp.pad(a, ((0, 0), (0, 0), (p, p), (0, 0))) if p else a

    xw = wpad(x)
    topw = wpad(top) if top is not None else None
    botw = wpad(bot) if bot is not None else None
    ext = _weighted_ext(xw, topw, botw, lo, hi, hs_j)

    t_lo = -(-lo // s)  # ceil(lo / s)
    hs_min = min(heights)
    t_hi = (hs_min + lo - k) // s  # interior rows valid on EVERY shard
    if not overlap or (lo == 0 and hi == 0) or t_hi < t_lo:
        return _mask_rows(_conv_valid(ext, params, s, groups)[:, :o_max], o_j)

    # HALP schedule, weighted: the interior slab is bounded by the *thinnest*
    # shard (static shapes); rows past it come off the spliced ext buffer.
    pieces = []
    if t_lo > 0:
        slab = jnp.concatenate([topw, xw[:, : (t_lo - 1) * s - lo + k]], axis=1)
        pieces.append(_conv_valid(slab, params, s, groups)[:, :t_lo])
    pieces.append(_conv_valid(xw[:, t_lo * s - lo : t_hi * s - lo + k], params, s, groups))
    if t_hi + 1 < o_max:
        slab = ext[:, (t_hi + 1) * s :]
        pieces.append(_conv_valid(slab, params, s, groups)[:, : o_max - t_hi - 1])
    y = jnp.concatenate(pieces, axis=1) if len(pieces) > 1 else pieces[0]
    return _mask_rows(y, o_j)


def max_pool_spatial(
    x: jax.Array, k: int = 2, s: int = 2, axis_name: str = "sp",
    heights: Sequence[int] | None = None,
) -> jax.Array:
    """Spatially-sharded max pool (aligned shards need no halo when k == s).

    With ``heights`` the pool runs on the capacity-weighted padded layout:
    output heights are the input heights divided by the stride."""
    b, hs, w, c = x.shape
    if heights is not None:
        lo, hi = halo_sizes(k, s, 0)
        heights, _idx, hs_j = _heights_setup(heights, axis_name, lo, hi, s)
        if hs != max(heights):
            raise ValueError(f"block height {hs} != max shard height {max(heights)}")
        top, bot = _issue_halos_weighted(x, lo, hi, heights, hs_j, axis_name)
        ext = _weighted_ext(x, top, bot, lo, hi, hs_j)
        y = lax.reduce_window(
            ext, -jnp.inf, lax.max, (1, k, k, 1), (1, s, s, 1), "VALID"
        )
        return _mask_rows(y[:, : hs // s], hs_j // s)
    if hs % s:
        raise ValueError("shard not aligned to pool stride")
    lo, hi = halo_sizes(k, s, 0)
    x = exchange_halos(x, lo, hi, axis_name)
    y = lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1), (1, s, s, 1), "VALID")
    return y[:, : hs // s]
