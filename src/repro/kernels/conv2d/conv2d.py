"""Pallas TPU kernel: direct 2-D convolution as MXU matmuls.

The paper's compute hot-spot is the conv layer; on TPU the idiomatic form is a
*direct* conv over VMEM-resident row tiles, where each (ky, kx) kernel tap is
one [TILE_H * W, C_in] x [C_in, C_out_tile] matmul on the MXU (an implicit
im2col that never materialises the patch matrix in HBM).

Tiling: the wrapper (ops.py) pre-builds overlapping row tiles -- the explicit
halo materialisation mirrors HALP's boundary rows -- so the kernel sees clean,
non-overlapping BlockSpec blocks:

    x_tiles [N, nT, (TH-1)*s + k, W_ext, C_in] -> block (1, 1, ..., Cin)
    weights [k, k, C_in, C_out]                -> block (k, k, Cin, TC)
    out     [N, nT, TH, W_out, C_out]          -> block (1, 1, TH, W_out, TC)

Grid: (N, nT, C_out / TC).  The wrapper picks TH so the per-step working set
(tile-padded, double-buffered blocks plus f32 temporaries) stays within
Mosaic's 16 MiB scoped-VMEM limit.

Generality (the spatial fast path needs all of it -- see ISSUE/ROADMAP 5):

* ``stride`` > 1: each tap gathers a strided patch from the row tile, so
  every VGG-16 / ConvNeXt stem+downsample conv lowers to the same kernel;
* depthwise convs (``groups == C_in == C_out``, weights [k, k, 1, C]): the
  tap matmul degenerates to a VPU multiply-accumulate over the channel axis;
* ragged row counts: tiles may overhang the tensor -- the wrapper pads the
  overhang with zeros and slices the surplus output rows off, so tile heights
  no longer need to divide the output height (remainder rows were previously
  *dropped silently*; see tests/test_kernels.py regression pins).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv_kernel(x_ref, w_ref, o_ref, *, k: int, th: int, w_out: int,
                 stride: int, depthwise: bool):
    """One (batch, row-tile, cout-tile) grid step."""
    cin = x_ref.shape[-1]
    tc = o_ref.shape[-1]
    s = stride
    blk = x_ref[0, 0]  # [(TH-1)*s + k, W_ext, Cin]
    if depthwise:
        acc = jnp.zeros((th, w_out, tc), jnp.float32)
    else:
        acc = jnp.zeros((th * w_out, tc), jnp.float32)
    for ky in range(k):
        for kx in range(k):
            # [TH, W_out, Cin] patch for this tap (strided when s > 1)
            patch = blk[
                ky : ky + (th - 1) * s + 1 : s,
                kx : kx + (w_out - 1) * s + 1 : s,
                :,
            ].astype(jnp.float32)
            if depthwise:
                # one input channel per output channel: a VPU mul-add, no MXU
                acc += patch * w_ref[ky, kx, 0, :].astype(jnp.float32)
            else:
                taps = w_ref[ky, kx, :, :]  # [Cin, TC]
                acc += jnp.dot(
                    patch.reshape(th * w_out, cin),
                    taps.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                )
    o_ref[0, 0] = acc.reshape(th, w_out, tc).astype(o_ref.dtype)


def conv2d_tiles(
    x_tiles: jax.Array,  # [N, nT, (TH-1)*stride + k, W_ext, Cin]
    weights: jax.Array,  # [k, k, Cin, Cout] ([k, k, 1, C] depthwise)
    *,
    k: int,
    tile_h: int,
    cout_tile: int,
    stride: int = 1,
    groups: int = 1,
    interpret: bool = False,
) -> jax.Array:
    n, nt, th_ext, w_ext, cin = x_tiles.shape
    cout = weights.shape[-1]
    w_out = (w_ext - k) // stride + 1
    assert th_ext == (tile_h - 1) * stride + k, (th_ext, tile_h, stride, k)
    assert cout % cout_tile == 0
    depthwise = groups > 1
    if depthwise:
        if not (groups == cin == cout and weights.shape[2] == 1):
            raise ValueError(
                f"grouped conv supported only for depthwise (groups == Cin == "
                f"Cout); got groups={groups} Cin={cin} Cout={cout}"
            )
        # the tap product is per-channel, so the channel tile must carry the
        # matching input channels -- keep the whole axis in one block
        cout_tile = cout

    kernel = functools.partial(
        _conv_kernel, k=k, th=tile_h, w_out=w_out, stride=stride,
        depthwise=depthwise,
    )
    return pl.pallas_call(
        kernel,
        grid=(n, nt, cout // cout_tile),
        in_specs=[
            pl.BlockSpec(
                (1, 1, th_ext, w_ext, cin), lambda b, t, c: (b, t, 0, 0, 0)
            ),
            pl.BlockSpec(
                (k, k, weights.shape[2], cout_tile), lambda b, t, c: (0, 0, 0, c)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tile_h, w_out, cout_tile), lambda b, t, c: (b, t, 0, 0, c)
        ),
        out_shape=jax.ShapeDtypeStruct((n, nt, tile_h, w_out, cout), x_tiles.dtype),
        interpret=interpret,
    )(x_tiles, weights)
