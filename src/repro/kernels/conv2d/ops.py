"""jit'd wrapper for the Pallas direct-conv kernel: padding, halo-tile
construction (the HALP boundary rows, materialised), VMEM budget heuristics."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .conv2d import conv2d_tiles

# Mosaic's default scoped-VMEM limit is 16 MiB per kernel; keep a margin for
# the relayout scratch the estimate below does not model.
VMEM_BUDGET = 12 * 1024 * 1024


def _pick_cout_tile(cout: int) -> int:
    """Largest divisor of ``cout`` that fits one MXU lane tile (<= 128)."""
    for tc in range(min(cout, 128), 0, -1):
        if cout % tc == 0:
            return tc
    return 1  # pragma: no cover - range above always yields a divisor


def _tiled(rows: int, lanes: int, itemsize: int = 4) -> int:
    """Elements a [rows, lanes] slab occupies in VMEM: the last two dims of
    every VMEM buffer are padded up to the native tile, (8, 128) for 32-bit
    elements; narrower ones pack more rows per tile ((16, 128) for bf16,
    (32, 128) for int8)."""
    sub = 8 * 4 // itemsize
    return -(-rows // sub) * sub * (-(-lanes // 128) * 128)


def _vmem_bytes(
    th: int, w_ext: int, cin: int, tc: int, k: int, itemsize: int, stride: int
) -> int:
    """Scoped VMEM one grid step of ``conv2d_tiles`` needs: the in/out blocks
    and the weight block, each double-buffered by the pipeline, plus the
    kernel's f32 temporaries (one tap's patch and the accumulator)."""
    w_out = (w_ext - k) // stride + 1
    x_blk = ((th - 1) * stride + k) * _tiled(w_ext, cin, itemsize)
    w_blk = k * k * _tiled(cin, tc, itemsize)
    o_blk = th * _tiled(w_out, tc, itemsize)
    patch = th * _tiled(w_out, cin)
    acc = _tiled(th * w_out, tc)
    return 2 * (x_blk + w_blk + o_blk) * itemsize + (patch + acc) * 4


def _pick_tile_h(
    h: int, w_ext: int, cin: int, cout: int, k: int, itemsize: int, stride: int = 1
):
    """Largest tile height (output rows) whose working set fits the VMEM
    budget.  Tiles need not divide ``h``: the kernel wrappers zero-pad the
    final (remainder) tile and slice the surplus rows off, so a prime-height
    shard no longer collapses to 1-row tiles (nor -- worse -- silently loses
    its remainder rows; see tests/test_kernels.py)."""
    tc = _pick_cout_tile(cout)
    for th in (64, 32, 16, 8, 4, 2, 1):
        if th > max(1, h):
            continue
        if _vmem_bytes(th, w_ext, cin, tc, k, itemsize, stride) <= VMEM_BUDGET:
            return th
    return 1


def _tile_rows(x: jax.Array, n_out: int, th: int, k: int, stride: int) -> jax.Array:
    """Stack overlapping row tiles: tile t covers output rows [t*th, t*th+th),
    i.e. input rows [t*th*s, t*th*s + (th-1)*s + k).  The input is zero-padded
    at the bottom so the last tile may overhang (remainder handling)."""
    nt = -(-n_out // th)  # ceil
    tile_ext = (th - 1) * stride + k
    need_rows = (nt - 1) * th * stride + tile_ext
    if need_rows > x.shape[1]:
        x = jnp.pad(x, ((0, 0), (0, need_rows - x.shape[1]), (0, 0), (0, 0)))
    idx = (jnp.arange(nt) * th * stride)[:, None] + jnp.arange(tile_ext)[None]
    return x[:, idx]  # [N, nT, tile_ext, W_ext, Cin]


def conv2d_pallas(
    x: jax.Array,  # [N, H, W, Cin]  (NHWC)
    weights: jax.Array,  # [k, k, Cin, Cout]
    bias: jax.Array | None = None,
    *,
    stride: int = 1,
    padding: int = 1,
    groups: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """SAME/VALID conv via the Pallas kernel (k = weights.shape[0])."""
    k = weights.shape[0]
    n, h, w, cin = x.shape
    cout = weights.shape[-1]
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    h_eff = (x.shape[1] - k) // stride + 1  # output rows
    w_ext = x.shape[2]
    th = _pick_tile_h(h_eff, w_ext, cin, cout, k, x.dtype.itemsize, stride)
    x_tiles = _tile_rows(x, h_eff, th, k, stride)
    nt = x_tiles.shape[1]
    y = conv2d_tiles(
        x_tiles, weights, k=k, tile_h=th, cout_tile=_pick_cout_tile(cout),
        stride=stride, groups=groups, interpret=interpret,
    )
    y = y.reshape(n, nt * th, (w_ext - k) // stride + 1, cout)[:, :h_eff]
    if bias is not None:
        y = y + bias
    return y
