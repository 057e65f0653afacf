"""Arch registry: every model configuration the system can serve or trace,
selectable by name.

An :class:`Arch` carries the *exact* published full config, a reduced smoke
config (same family, tiny dims) and the model module whose ``init`` and
``apply`` (``forward`` / ``decode_step`` for the LMs) build and run it.  The
serving launcher (``repro.launch.serve.build_model``), the chip benchmark and
the tests read ``cfg``, ``smoke_cfg`` and ``module``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

__all__ = ["Arch", "REGISTRY", "register", "get", "list_archs"]


@dataclass(frozen=True)
class Arch:
    name: str
    family: str  # "lm" | "vision" | "diffusion" | "convnet"
    cfg: Any
    smoke_cfg: Any
    module: Any  # the model module (init, apply or forward)
    notes: str = ""


REGISTRY: dict[str, Arch] = {}


def register(arch: Arch) -> Arch:
    REGISTRY[arch.name] = arch
    return arch


def get(name: str) -> Arch:
    if name not in REGISTRY:
        # import side-effect registration
        from . import _load_all  # noqa

        _load_all()
    return REGISTRY[name]


def list_archs() -> list[str]:
    from . import _load_all

    _load_all()
    return sorted(REGISTRY)
