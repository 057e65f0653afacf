"""dit-xl2 [diffusion]: img_res=256 patch=2 n_layers=28 d_model=1152
n_heads=16.  [arXiv:2212.09748; paper]"""
from ..models import dit
from ..models.dit import DiTConfig
from .base import Arch, register

FULL = DiTConfig(name="dit-xl2", img_res=256, patch=2, n_layers=28,
                 d_model=1152, n_heads=16)
SMOKE = DiTConfig(name="dit-xl2-smoke", img_res=64, patch=2, n_layers=2,
                  d_model=64, n_heads=4, num_classes=10)

ARCH = register(
    Arch(
        name="dit-xl2",
        family="diffusion",
        cfg=FULL,
        smoke_cfg=SMOKE,
        module=dit,
        notes="latent diffusion transformer",
    )
)
