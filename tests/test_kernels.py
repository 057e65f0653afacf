"""Pallas kernel tests: interpret=True (CPU) vs. pure-jnp oracles, with
shape/dtype sweeps per kernel as the deliverable requires."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.attention import attention_ref, flash_attention, gqa_flash
from repro.kernels.conv2d import conv2d_pallas, conv2d_ref
from repro.kernels.halo_conv import halo_conv2d, halo_conv2d_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

CONV_CASES = [
    # (N, H, W, Cin, Cout, k, pad)
    (1, 16, 16, 8, 16, 3, 1),
    (2, 32, 24, 16, 32, 3, 1),
    (1, 8, 8, 4, 8, 1, 0),
    (1, 20, 20, 8, 16, 5, 2),
    (2, 14, 14, 32, 64, 3, 1),  # VGG-16 deep-layer-like
    (1, 17, 13, 3, 8, 3, 1),  # odd sizes
]


@pytest.mark.parametrize(
    "itemsize,sublanes", [(4, 8), (2, 16), (1, 32)], ids=["f32", "bf16", "int8"]
)
def test_vmem_tile_packs_narrow_rows(itemsize, sublanes):
    """A VMEM slab pads its last two dims to the dtype's native tile: narrow
    dtypes pack more rows per tile, so a 3-row slab of them takes more."""
    from repro.kernels.conv2d.ops import _tiled

    assert _tiled(3, 3, itemsize) == sublanes * 128
    assert _tiled(sublanes + 1, 129, itemsize) == 2 * sublanes * 256


@pytest.mark.parametrize("case", CONV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_kernel_matches_ref(case, dtype):
    n, h, w, cin, cout, k, pad = case
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (n, h, w, cin), jnp.float32).astype(dtype)
    wts = (0.1 * jax.random.normal(kw, (k, k, cin, cout), jnp.float32)).astype(dtype)
    b = jax.random.normal(kb, (cout,), jnp.float32).astype(dtype)
    got = conv2d_pallas(x, wts, b, padding=pad, interpret=True)
    want = conv2d_ref(x, wts, b, padding=pad)
    assert got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_conv2d_matches_lax_conv():
    """Cross-check the oracle itself against lax.conv_general_dilated."""
    from jax import lax

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 8))
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 8, 16)) * 0.1
    want = lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    got = conv2d_ref(x, w, padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@given(
    h=st.integers(6, 24),
    w=st.integers(6, 24),
    cin=st.sampled_from([3, 4, 8]),
    cout=st.sampled_from([8, 16]),
    k=st.sampled_from([1, 3, 5]),
)
@settings(max_examples=25, deadline=None)
def test_conv2d_kernel_property(h, w, cin, cout, k):
    pad = k // 2
    x = jax.random.normal(jax.random.PRNGKey(h * w), (1, h, w, cin))
    wts = 0.1 * jax.random.normal(jax.random.PRNGKey(k), (k, k, cin, cout))
    got = conv2d_pallas(x, wts, padding=pad, interpret=True)
    want = conv2d_ref(x, wts, padding=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, H, T, S, D, causal)
    (1, 2, 128, 128, 32, True),
    (2, 4, 256, 256, 64, True),
    (1, 2, 128, 128, 32, False),
    (1, 1, 64, 64, 16, True),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    b, h, t, s, d, causal = case
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, t, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, h, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (b, h, s, d), jnp.float32).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64, interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_gqa_flash_matches_model_sdpa():
    """GQA wrapper vs. the model's grouped _sdpa (the production oracle)."""
    from repro.models.attention import _sdpa

    b, t, h, hkv, d = 2, 128, 8, 2, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, hkv, d))
    v = jax.random.normal(kv, (b, t, hkv, d))
    mask = jnp.tril(jnp.ones((t, t), bool))[None, None, None]
    want = _sdpa(q, k, v, mask, d**-0.5)
    got = gqa_flash(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t", [64, 192, 256])
def test_flash_attention_block_sweep(t):
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, t, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, t, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, t, 32))
    want = attention_ref(q, k, v, causal=True)
    for qb, kb in ((32, 64), (64, 32), (64, 64)):
        if t % qb or t % kb:
            continue
        got = flash_attention(q, k, v, causal=True, q_block=qb, kv_block=kb, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# halo conv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,pad", [(3, 1), (5, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_halo_conv_matches_ref(k, pad, dtype):
    b, hs, w, cin, cout = 2, 16, 12, 8, 16
    lo, hi = pad, k - 1 - pad
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (b, hs, w, cin), jnp.float32).astype(dtype)
    top = jax.random.normal(keys[1], (b, lo, w, cin), jnp.float32).astype(dtype)
    bot = jax.random.normal(keys[2], (b, hi, w, cin), jnp.float32).astype(dtype)
    wts = (0.1 * jax.random.normal(keys[3], (k, k, cin, cout), jnp.float32)).astype(dtype)
    got = halo_conv2d(x, top, bot, wts, padding=pad, interpret=True)
    want = halo_conv2d_ref(x, top, bot, wts, padding=pad)
    # the reference computes the full extended conv; our op returns the shard rows
    want = want[:, : hs]
    assert got.shape == want.shape, (got.shape, want.shape)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


STRIDED_CASES = [
    # (N, H, W, Cin, Cout, k, stride, pad)
    (1, 16, 16, 8, 16, 3, 2, 1),
    (1, 64, 64, 3, 16, 7, 2, 3),  # ResNet/EfficientNet stem
    (2, 32, 32, 4, 8, 2, 2, 0),   # pool-like conv
    (1, 20, 20, 8, 16, 5, 2, 2),
    (1, 17, 13, 3, 8, 3, 2, 1),   # odd sizes, strided
]


@pytest.mark.parametrize("case", STRIDED_CASES)
def test_conv2d_kernel_strided(case):
    n, h, w, cin, cout, k, s, pad = case
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n, h, w, cin))
    wts = 0.1 * jax.random.normal(kw, (k, k, cin, cout))
    got = conv2d_pallas(x, wts, stride=s, padding=pad, interpret=True)
    want = conv2d_ref(x, wts, stride=s, padding=pad)
    assert got.shape == want.shape, (got.shape, want.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k,stride", [(3, 1), (7, 1), (3, 2)])
def test_conv2d_kernel_depthwise(k, stride):
    """Depthwise path (groups == cin == cout): VPU mul-add, no MXU matmul."""
    c, pad = 8, k // 2
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (1, 24, 20, c))
    wts = 0.1 * jax.random.normal(kw, (k, k, 1, c))
    got = conv2d_pallas(x, wts, stride=stride, padding=pad, groups=c, interpret=True)
    want = conv2d_ref(x, wts, stride=stride, padding=pad, groups=c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_conv2d_kernel_rejects_grouped_non_depthwise():
    x = jnp.zeros((1, 8, 8, 8))
    wts = jnp.zeros((3, 3, 4, 8))  # groups=2: neither dense nor depthwise
    with pytest.raises(ValueError, match="depthwise"):
        conv2d_pallas(x, wts, padding=1, groups=2, interpret=True)


@pytest.mark.parametrize("k,stride,pad", [(3, 1, 1), (5, 1, 2), (3, 2, 1), (5, 2, 3), (7, 2, 3)])
def test_halo_conv_stride_sweep(k, stride, pad):
    """Acceptance sweep: fused kernel vs concat-then-conv oracle for k in
    {3,5,7}, stride in {1,2} with exact halos lo + hi == k - s."""
    b, hs, w, cin, cout = 1, 16, 11, 4, 8
    lo, hi = pad, k - pad - stride
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(keys[0], (b, hs, w, cin))
    top = jax.random.normal(keys[1], (b, lo, w, cin)) if lo else None
    bot = jax.random.normal(keys[2], (b, hi, w, cin)) if hi else None
    wts = 0.1 * jax.random.normal(keys[3], (k, k, cin, cout))
    got = halo_conv2d(x, top, bot, wts, stride=stride, padding=pad, interpret=True)
    want = halo_conv2d_ref(x, top, bot, wts, stride=stride, padding=pad)
    assert got.shape == want.shape, (got.shape, want.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hs,tile_h", [(10, 4), (16, 6), (7, 3)])
def test_halo_conv_remainder_tiles(hs, tile_h):
    """Regression pin: hs % tile_h != 0 must NOT drop the remainder rows.

    The pre-fix tiling used ``nt = hs // th``, silently truncating the shard's
    output; the ceil-tiling path must produce every row, bit-close to the
    oracle."""
    assert hs % tile_h != 0  # the case under test
    b, w, cin, cout, k, pad = 1, 9, 4, 8, 3, 1
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (b, hs, w, cin))
    top = jax.random.normal(keys[1], (b, pad, w, cin))
    bot = jax.random.normal(keys[2], (b, k - 1 - pad, w, cin))
    wts = 0.1 * jax.random.normal(keys[3], (k, k, cin, cout))
    got = halo_conv2d(x, top, bot, wts, padding=pad, tile_h=tile_h, interpret=True)
    want = halo_conv2d_ref(x, top, bot, wts, padding=pad)
    assert got.shape[1] == hs, got.shape  # every output row present
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_halo_conv_rejects_inexact_halos():
    x = jnp.zeros((1, 8, 8, 4))
    wts = jnp.zeros((3, 3, 4, 8))
    with pytest.raises(ValueError, match="lo \\+ hi"):
        halo_conv2d(x, jnp.zeros((1, 1, 8, 4)), jnp.zeros((1, 2, 8, 4)), wts,
                    padding=1, interpret=True)


def test_halo_conv_equals_unsharded_conv():
    """Two half-shards with exchanged halos == one unsharded conv (HALP
    losslessness at kernel level)."""
    b, h, w, cin, cout = 1, 32, 16, 4, 8
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (b, h, w, cin))
    wts = 0.1 * jax.random.normal(kw, (3, 3, cin, cout))
    want = conv2d_ref(x, wts, padding=1)
    top_shard, bot_shard = x[:, : h // 2], x[:, h // 2 :]
    zeros = jnp.zeros((b, 1, w, cin))
    y_top = halo_conv2d(top_shard, zeros, bot_shard[:, :1], wts, padding=1, interpret=True)
    y_bot = halo_conv2d(bot_shard, top_shard[:, -1:], zeros, wts, padding=1, interpret=True)
    got = jnp.concatenate([y_top, y_bot], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
