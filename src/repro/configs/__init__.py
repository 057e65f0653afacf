"""Config registry: one module per assigned architecture (+ the paper's VGG-16).

``get(name)`` / ``list_archs()`` trigger registration lazily.
"""
from .base import Arch, REGISTRY, get, list_archs  # noqa: F401

_LOADED = False


def _load_all():
    global _LOADED
    if _LOADED:
        return
    from . import (  # noqa: F401
        codeqwen15_7b,
        convnext_b,
        deepseek_v3_671b,
        dit_xl2,
        efficientnet_b7,
        moonshot_v1_16b_a3b,
        qwen3_4b,
        swin_b,
        unet_sd15,
        vgg16,
        vit_l16,
    )
    _LOADED = True
