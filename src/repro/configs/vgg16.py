"""vgg16 -- the paper's own evaluation model, served through the HALP plan
and used by the reproduction benchmarks and examples."""
from ..models import vgg
from ..models.vgg import VGGConfig
from .base import Arch, register

FULL = VGGConfig()
SMOKE = VGGConfig(img_res=64, width_mult=0.125, num_classes=10)

ARCH = register(
    Arch(
        name="vgg16",
        family="convnet",
        cfg=FULL,
        smoke_cfg=SMOKE,
        module=vgg,
        notes="paper model; served through the HALP spatial engine",
    )
)
