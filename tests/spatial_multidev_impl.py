"""Multi-device spatial-parallelism checks; run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (see test_spatial.py).
Exits non-zero on any mismatch."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.models import vgg
from repro.models.layers import conv2d, max_pool, relu
from repro.spatial import (
    conv2d_spatial,
    max_pool_spatial,
    merge_padded_shards,
    shard_heights,
    to_padded_shards,
)
from repro.models.common import conv_params

assert len(jax.devices()) == 8, jax.devices()
mesh = Mesh(np.array(jax.devices()).reshape(8), ("sp",))


def check(name, got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=name)
    print(f"ok: {name}")


# --- single conv, sweep of geometries, both schedules -----------------------
key = jax.random.PRNGKey(0)
for (k, s, p, c_in, c_out, h) in [
    (3, 1, 1, 3, 16, 64),     # VGG body
    (1, 1, 0, 8, 16, 32),     # pointwise
    (5, 1, 2, 4, 8, 64),      # 5x5 (paper-bug regime handled exactly)
    (7, 2, 3, 3, 16, 64),     # ResNet/EfficientNet stem
    (3, 2, 1, 8, 8, 64),      # strided 3x3
    (2, 2, 0, 4, 4, 32),      # pool-like conv
]:
    kp, kx, key = (*jax.random.split(key, 2), key)
    params = conv_params(kp, k, c_in, c_out)
    x = jax.random.normal(kx, (2, h, h, c_in))
    want = conv2d(x, params, stride=s, padding=[(p, p), (p, p)])
    for overlap in (False, True):
        fn = shard_map(
            partial(conv2d_spatial, k=k, s=s, p=p, axis_name="sp", overlap=overlap),
            mesh=mesh,
            in_specs=(P(None, "sp", None, None), P()),
            out_specs=P(None, "sp", None, None),
        )
        got = fn(x, params)
        check(f"conv k{k}s{s}p{p} overlap={overlap}", got, want)

# --- depthwise conv (EfficientNet / ConvNeXt path) --------------------------
kp, kx, key = (*jax.random.split(key, 2), key)
c = 8
params = conv_params(kp, 7, c, c, groups=c)
x = jax.random.normal(kx, (1, 56, 56, c))
want = conv2d(x, params, stride=1, padding=[(3, 3), (3, 3)], groups=c)
fn = shard_map(
    partial(conv2d_spatial, k=7, s=1, p=3, axis_name="sp", overlap=True, groups=c),
    mesh=mesh,
    in_specs=(P(None, "sp", None, None), P()),
    out_specs=P(None, "sp", None, None),
)
check("depthwise 7x7", fn(x, params), want)

# --- fused Pallas engine: same geometry sweep through ONE pallas_call --------
# (pallas_call has no shard_map replication rule -> check_vma=False)
key = jax.random.PRNGKey(21)
for (k, s, p, c_in, c_out, h, g) in [
    (3, 1, 1, 3, 16, 64, 1),
    (1, 1, 0, 8, 16, 32, 1),
    (5, 1, 2, 4, 8, 64, 1),
    (7, 2, 3, 3, 16, 64, 1),
    (3, 2, 1, 8, 8, 64, 1),
    (2, 2, 0, 4, 4, 32, 1),
    (7, 1, 3, 8, 8, 56, 8),  # depthwise through the kernel's VPU path
]:
    kp, kx, key = (*jax.random.split(key, 2), key)
    params = conv_params(kp, k, c_in, c_out, groups=g)
    x = jax.random.normal(kx, (2, h, h, c_in))
    want = conv2d(x, params, stride=s, padding=[(p, p), (p, p)], groups=g)
    fn = shard_map(
        partial(conv2d_spatial, k=k, s=s, p=p, axis_name="sp", groups=g,
                engine="pallas", interpret=True),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None), P()),
        out_specs=P(None, "sp", None, None),
        check_vma=False,
    )
    check(f"pallas conv k{k}s{s}p{p}g{g}", fn(x, params), want)

# --- thin-shard fallback: t_hi < t_lo (no interior rows at 4-row shards) -----
kp, kx, key = (*jax.random.split(key, 2), key)
params = conv_params(kp, 7, 4, 8)
x = jax.random.normal(kx, (1, 32, 16, 4))  # 8 shards x 4 rows, lo = hi = 3
want = conv2d(x, params, stride=1, padding=[(3, 3), (3, 3)])
for engine in ("lax", "pallas"):
    fn = shard_map(
        partial(conv2d_spatial, k=7, s=1, p=3, axis_name="sp", overlap=True,
                engine=engine, interpret=True),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None), P()),
        out_specs=P(None, "sp", None, None),
        check_vma=False,
    )
    check(f"thin-shard k7 (t_hi < t_lo) {engine}", fn(x, params), want)

# --- capacity-weighted shards: skewed split in the padded equal-block layout -
H = 64
hts = shard_heights(H, 8, ratios=[4, 3, 2, 1, 1, 2, 3, 4], align=2)
assert sum(hts) == H and max(hts) > min(hts), hts
for (k, s, p, c_in, c_out, g) in [
    (3, 1, 1, 3, 8, 1),
    (5, 1, 2, 4, 8, 1),   # 5x5 boundary slabs, weighted
    (3, 2, 1, 8, 8, 1),
    (7, 2, 3, 3, 8, 1),
    (7, 1, 3, 8, 8, 8),   # depthwise (groups > 1) boundary slabs, weighted
]:
    kp, kx, key = (*jax.random.split(key, 2), key)
    params = conv_params(kp, k, c_in, c_out, groups=g)
    x = jax.random.normal(kx, (2, H, 17, c_in))
    want = conv2d(x, params, stride=s, padding=[(p, p), (p, p)], groups=g)
    xp = to_padded_shards(x, hts)
    o_hts = tuple(hh // s for hh in hts)
    for engine, overlap in (("lax", True), ("lax", False), ("pallas", True)):
        fn = shard_map(
            partial(conv2d_spatial, k=k, s=s, p=p, axis_name="sp",
                    overlap=overlap, groups=g, engine=engine, interpret=True,
                    heights=hts),
            mesh=mesh,
            in_specs=(P(None, "sp", None, None), P()),
            out_specs=P(None, "sp", None, None),
            check_vma=False,
        )
        got = merge_padded_shards(fn(xp, params), o_hts)
        check(f"weighted conv k{k}s{s}p{p}g{g} {engine} ov={overlap}", got, want)

# --- taller weighted shards: every geometry above takes the *overlapped*
# bottom-halo pallas path (min height >= n_fix*s + lo, so the kernel runs
# without the pre-kernel bottom splice and the fix-up conv patches the edge)
hts_tall = (10, 8, 6, 6, 6, 8, 10, 10)  # sum 64, all even, min 6
assert sum(hts_tall) == H and min(hts_tall) >= 6  # k7s1p3: n_fix*s + lo = 6
for (k, s, p, c_in, c_out, g) in [
    (3, 1, 1, 3, 8, 1),
    (5, 1, 2, 4, 8, 1),
    (7, 2, 3, 3, 8, 1),
    (7, 1, 3, 8, 8, 8),   # depthwise overlapped fix-up
]:
    kp, kx, key = (*jax.random.split(key, 2), key)
    params = conv_params(kp, k, c_in, c_out, groups=g)
    x = jax.random.normal(kx, (2, H, 17, c_in))
    want = conv2d(x, params, stride=s, padding=[(p, p), (p, p)], groups=g)
    fn = shard_map(
        partial(conv2d_spatial, k=k, s=s, p=p, axis_name="sp",
                overlap=True, groups=g, engine="pallas", interpret=True,
                heights=hts_tall),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None), P()),
        out_specs=P(None, "sp", None, None),
        check_vma=False,
    )
    got = merge_padded_shards(fn(to_padded_shards(x, hts_tall), params),
                              tuple(hh // s for hh in hts_tall))
    check(f"weighted-tall overlapped-bottom k{k}s{s}p{p}g{g}", got, want)

# weighted max pool: k == s (no halo) and k > s (bottom-halo path)
x = jax.random.normal(key, (2, H, 16, 4))
xp = to_padded_shards(x, hts)
from jax import lax as _lax

for (k, s) in [(2, 2), (3, 2)]:
    xe = jnp.concatenate([x, jnp.zeros((2, k - s, 16, 4))], axis=1)
    want = _lax.reduce_window(
        xe, -jnp.inf, _lax.max, (1, k, k, 1), (1, s, s, 1), "VALID"
    )
    fn = shard_map(
        partial(max_pool_spatial, k=k, s=s, axis_name="sp", heights=hts),
        mesh=mesh,
        in_specs=P(None, "sp", None, None),
        out_specs=P(None, "sp", None, None),
    )
    got = merge_padded_shards(fn(xp), tuple(hh // s for hh in hts))
    check(f"weighted maxpool k{k}s{s}", got, want)

# --- max pool ----------------------------------------------------------------
x = jax.random.normal(key, (2, 64, 64, 4))
want = max_pool(x, 2, 2)
fn = shard_map(
    partial(max_pool_spatial, k=2, s=2, axis_name="sp"),
    mesh=mesh,
    in_specs=P(None, "sp", None, None),
    out_specs=P(None, "sp", None, None),
)
check("maxpool 2x2", fn(x), want)

# --- full VGG feature extractor under shard_map ------------------------------
cfg = vgg.VGGConfig(img_res=64, width_mult=0.125, num_classes=10)
params = vgg.init(jax.random.PRNGKey(3), cfg)
x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64, 3))
want = vgg.features(params, cfg, x)


def spatial_features(x, feats):
    geom = cfg.geom()
    for p_l, g in zip(feats, geom.layers):
        if g.kind == "pool":
            x = max_pool_spatial(x, g.k, g.s, axis_name="sp")
        else:
            x = relu(conv2d_spatial(x, p_l, g.k, g.s, g.p, axis_name="sp", overlap=True))
    return x


fn = shard_map(
    spatial_features,
    mesh=mesh,
    in_specs=(P(None, "sp", None, None), P()),
    out_specs=P(None, "sp", None, None),
)
# 64 rows / 8 devices = 8 rows per shard; after 4 pools the shard is 4/8... the
# last block would underflow 1 row/shard -> run on the first 3 blocks instead.
cfg_sp = vgg.VGGConfig(img_res=64, width_mult=0.125, num_classes=10,
                       blocks=((2, 64), (2, 128), (3, 256)))
params_sp = vgg.init(jax.random.PRNGKey(3), cfg_sp)
want_sp = vgg.features(params_sp, cfg_sp, x)


def spatial_features_sp(x, feats):
    geom = cfg_sp.geom()
    for p_l, g in zip(feats, geom.layers):
        if g.kind == "pool":
            x = max_pool_spatial(x, g.k, g.s, axis_name="sp")
        else:
            x = relu(conv2d_spatial(x, p_l, g.k, g.s, g.p, axis_name="sp", overlap=True))
    return x


fn = shard_map(
    spatial_features_sp,
    mesh=mesh,
    in_specs=(P(None, "sp", None, None), P()),
    out_specs=P(None, "sp", None, None),
)
check("vgg features (3 blocks, 8-way SP)", fn(x, params_sp["features"]), want_sp)

# --- full weighted VGG stack through the fused engine ------------------------
# 2 blocks -> stride alignment 4; 8-way skewed split of 64 rows.
cfg_w = vgg.VGGConfig(img_res=64, width_mult=0.125, num_classes=10,
                      blocks=((2, 64), (2, 128)))
params_w = vgg.init(jax.random.PRNGKey(5), cfg_w)
hts_w = shard_heights(64, 8, ratios=[4, 3, 2, 1, 1, 2, 3, 4], align=4)
assert max(hts_w) > min(hts_w), hts_w
want_w = vgg.features(params_w, cfg_w, x)


def spatial_features_weighted(xs, feats):
    hts = hts_w
    for p_l, g in zip(feats, cfg_w.geom().layers):
        if g.kind == "pool":
            xs = max_pool_spatial(xs, g.k, g.s, axis_name="sp", heights=hts)
        else:
            xs = relu(conv2d_spatial(xs, p_l, g.k, g.s, g.p, axis_name="sp",
                                     overlap=True, engine="pallas",
                                     interpret=True, heights=hts))
        hts = tuple(hh // g.s for hh in hts)
    return xs


fn = shard_map(
    spatial_features_weighted,
    mesh=mesh,
    in_specs=(P(None, "sp", None, None), P()),
    out_specs=P(None, "sp", None, None),
    check_vma=False,
)
got_w = merge_padded_shards(
    fn(to_padded_shards(x, hts_w), params_w["features"]),
    tuple(hh // 4 for hh in hts_w),
)
check("vgg features weighted+fused (2 blocks, skewed 8-way)", got_w, want_w)

print("ALL MULTIDEV SPATIAL CHECKS PASSED")

# --- pipeline parallelism over 8 stages --------------------------------------
from repro.parallel.pipeline import pipeline_apply

S = 8
D = 16
M = 6
key = jax.random.PRNGKey(7)
ws = jax.random.normal(key, (S, D, D)) * 0.3
xs = jax.random.normal(jax.random.PRNGKey(8), (M, 4, D))

def stage_fn(w, x):
    return jnp.tanh(x @ w)

# reference: sequential through all stages
ref = xs
for i in range(S):
    ref = jax.vmap(lambda mb: stage_fn(ws[i], mb))(ref)

pipe = shard_map(
    lambda w, x: pipeline_apply(w[0], x, stage_fn, "sp"),  # drop the stage dim
    mesh=mesh,
    in_specs=(P("sp"), P()),       # one stage's weights per device
    out_specs=P(),                  # outputs valid on the last stage
    check_vma=False,
)
got = pipe(ws, xs)
check("pipeline 8-stage forward", got, ref, tol=1e-4)

print("ALL MULTIDEV CHECKS PASSED (incl. pipeline)")
