"""swin-b [vision]: img_res=224 patch=4 window=7 depths=2-2-18-2
dims=128-256-512-1024; swin-b-384: the same network fine-tuned at 384 px
with window 12 (the authors' swin_base_patch4_window12_384).
[arXiv:2103.14030; paper]"""
import dataclasses

from ..models import swin
from ..models.swin import SwinConfig
from .base import Arch, register

FULL = SwinConfig(name="swin-b", img_res=224, patch=4, window=7,
                  depths=(2, 2, 18, 2), dims=(128, 256, 512, 1024),
                  n_heads=(4, 8, 16, 32))
SMOKE = SwinConfig(name="swin-b-smoke", img_res=64, patch=4, window=4,
                   depths=(2, 2), dims=(32, 64), n_heads=(2, 4), num_classes=10)

ARCH = register(
    Arch(
        name="swin-b",
        family="vision",
        cfg=FULL,
        smoke_cfg=SMOKE,
        module=swin,
        notes="bounded receptive field (7x7 windows): shifted windows need a "
        "one-window halo -- the transformer analogue of HALP's boundary "
        "exchange (swin-b-384 uses window 12)",
    )
)

# Stage 4 at 384 px is 12 x 12 tokens: one window, unshifted.
ARCH_384 = register(
    Arch(
        name="swin-b-384",
        family="vision",
        cfg=dataclasses.replace(FULL, name="swin-b-384", img_res=384, window=12),
        smoke_cfg=SMOKE,
        module=swin,
        notes="served configuration: 9,216-token stage 1 "
        "in 144-token shifted windows",
    )
)
