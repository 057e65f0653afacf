"""VGG-16 (Simonyan & Zisserman, ICLR'15) -- the paper's evaluation model.

The feature extractor is expressed as an explicit layer list aligned with
``repro.core.nets.vgg16_geom`` so the HALP partitioner can drive it
layer-by-layer (``repro.spatial.partition_apply``); the classifier head runs
after the final merge, exactly as the paper's FLs do on the host ES.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..core.nets import ConvNetGeom, vgg16_geom
from ..core.rf import LayerGeom, conv as geom_conv, pool as geom_pool
from .common import Params, conv_params, dense_params, keygen
from .layers import conv2d, dense, max_pool, relu, global_avg_pool


@dataclass(frozen=True)
class VGGConfig:
    name: str = "vgg16"
    img_res: int = 224
    in_channels: int = 3
    num_classes: int = 1000
    width_mult: float = 1.0  # reduced configs for CPU smoke tests
    blocks: tuple[tuple[int, int], ...] = ((2, 64), (2, 128), (3, 256), (3, 512), (3, 512))
    fc_dims: tuple[int, ...] = (4096, 4096)

    def widths(self) -> list[tuple[int, int]]:
        return [(reps, max(8, int(c * self.width_mult))) for reps, c in self.blocks]

    def geom(self) -> ConvNetGeom:
        layers: list[LayerGeom] = []
        c_in = self.in_channels
        for b, (reps, c_out) in enumerate(self.widths(), start=1):
            for r in range(1, reps + 1):
                layers.append(geom_conv(f"conv{b}_{r}", c_in, c_out, k=3, s=1, p=1))
                c_in = c_out
            layers.append(geom_pool(f"pool{b}", c_in))
        final_rows = self.img_res // (2 ** len(self.blocks))
        c_last = self.widths()[-1][1]
        dims = [c_last * final_rows * final_rows, *self.fc_dims, self.num_classes]
        head = sum(2.0 * a + 0.0 for a in [])  # placeholder, computed below
        head = sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))
        return ConvNetGeom(
            name=self.name,
            in_rows=self.img_res,
            in_channels=self.in_channels,
            layers=tuple(layers),
            head_flops=head,
        )


def init(key: jax.Array, cfg: VGGConfig) -> Params:
    ks = keygen(key)
    feats: list[Params] = []
    c_in = cfg.in_channels
    for reps, c_out in cfg.widths():
        for _ in range(reps):
            feats.append(conv_params(next(ks), 3, c_in, c_out))
            c_in = c_out
        feats.append({})  # pool layer: no params (keeps indices aligned w/ geom)
    final_rows = cfg.img_res // (2 ** len(cfg.blocks))
    dims = [c_in * final_rows * final_rows, *cfg.fc_dims, cfg.num_classes]
    head = [dense_params(next(ks), a, b) for a, b in zip(dims[:-1], dims[1:])]
    return {"features": feats, "head": head}


def apply_layer(params: Params, geom: LayerGeom, x: jax.Array) -> jax.Array:
    """One feature layer on (a slice of) the input -- 'VALID' padded.

    The caller supplies exactly the input rows the receptive field requires
    (plus explicit zero padding at true tensor edges), so the layer itself uses
    VALID padding.  This is the primitive both the single-device reference and
    every distributed execution path share.
    """
    if geom.kind == "pool":
        return max_pool(x, k=geom.k, s=geom.s)
    y = conv2d(x, params, stride=geom.s, padding="VALID")
    return relu(y)


def features(params: Params, cfg: VGGConfig, x: jax.Array) -> jax.Array:
    geom = cfg.geom()
    for p, g in zip(params["features"], geom.layers):
        if g.kind != "pool" and g.p:
            x = jnp.pad(x, ((0, 0), (g.p, g.p), (g.p, g.p), (0, 0)))
        x = apply_layer(p, g, x)
    return x


def head(params: Params, x: jax.Array) -> jax.Array:
    """Classifier logits; each dense layer under the scope ``head/fc<j>``."""
    with jax.named_scope("head"):
        x = x.reshape(x.shape[0], -1)
        hs = params["head"]
        for j, p in enumerate(hs, start=1):
            with jax.named_scope(f"fc{j}"):
                x = dense(x, p)
                if j < len(hs):
                    x = relu(x)
        return x


def apply(params: Params, cfg: VGGConfig, x: jax.Array) -> jax.Array:
    """Full forward: feature extractor + classifier logits."""
    return head(params, features(params, cfg, x))
