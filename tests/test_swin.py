"""Swin-B served by the program against its plain reference
(``bench/reference/swin-b-384.py``) at small sizes on the CPU, and the
published equations each side must keep: LayerNorm eps 1e-5, the exact GELU,
patch merging in the order x0, x1, x2, x3, -100 added to masked logits, and
the window tables as NumPy constants."""
import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch.serve as launch
from repro.configs import get
from repro.models import swin

REF_PATH = Path(__file__).resolve().parents[1] / "bench" / "reference" / "swin-b-384.py"
KEYS = ("img_res", "in_channels", "num_classes", "patch", "window", "depths", "dims",
        "n_heads", "mlp_ratio")
# three stages whose last is exactly one window, unshifted, as stage 4 is at 384 px
THREE = swin.SwinConfig(name="swin-three", img_res=96, patch=4, window=6, depths=(2, 2, 2),
                        dims=(16, 32, 64), n_heads=(2, 2, 4), num_classes=10)
CONFIGS = {"smoke": get("swin-b-384").smoke_cfg, "three": THREE}
# Both sides compute in float32 at the highest matmul precision, so they differ
# only by rounding in another order of summation: at most 4.2e-7 of the largest
# logit at these sizes.  1e-5 leaves twenty times that, and is seven times
# under the smallest change a reverted equation makes (the tanh GELU, 7.1e-5).
TOL = 1e-5


@pytest.fixture
def ref():
    spec = importlib.util.spec_from_file_location("swin_b_384_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def fresh_tables():
    swin._shift_mask.cache_clear()
    yield
    swin._shift_mask.cache_clear()


def _model(cfg):
    return {k: json.loads(json.dumps(getattr(cfg, k))) for k in KEYS}


def _served(monkeypatch, cfg):
    """The function ``build_model`` serves for ``cfg`` (as the registry's
    smoke configuration of ``swin-b-384``), called as ``f(weights, batch)``."""
    arch = dataclasses.replace(get("swin-b-384"), smoke_cfg=cfg)
    monkeypatch.setattr(launch, "get", lambda name: arch)
    got, _params, fn = launch.build_model("swin-b-384", smoke=True)
    assert got == cfg
    return fn.func


def _inputs(ref, cfg, sensitive):
    """Seeded weights and images.  ``sensitive``: images scaled by 1e-3, so
    the patch LayerNorm's inputs vary mostly by its bias and eps shows, and
    bias tables by 5000, so logits spread past 100 and masked keys keep some
    weight under -100."""
    m = _model(cfg)
    w = ref.weights(jax.random.PRNGKey(3), m)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, cfg.img_res, cfg.img_res, cfg.in_channels))
    if sensitive:
        x = 1e-3 * x
        for s in w["stages"]:
            s["blocks"]["rel_bias"] = 5000.0 * s["blocks"]["rel_bias"]
    return m, w, x


def _err(monkeypatch, ref, cfg, sensitive):
    m, w, x = _inputs(ref, cfg, sensitive)
    with jax.default_matmul_precision("highest"):
        y = _served(monkeypatch, cfg)(w, x)
    want = jax.jit(functools.partial(ref.forward, m=m, mode="highest"))(w, x)
    return float(jnp.abs(y - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("sensitive", [False, True])
@pytest.mark.parametrize("size", sorted(CONFIGS))
def test_program_matches_the_reference(monkeypatch, ref, size, sensitive):
    cfg = CONFIGS[size]
    m = _model(cfg)
    shapes = jax.eval_shape(functools.partial(ref.weights, m=m), jax.random.PRNGKey(0))
    params = jax.eval_shape(lambda k: swin.init(k, cfg), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, params)
    assert _err(monkeypatch, ref, cfg, sensitive) <= TOL


def _tanh_gelu(x):
    return jax.nn.gelu(x, approximate=True)


def _program_merge_x0_x2_x1_x3(stage, x):
    b, h, w, c = x.shape
    x = x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    return swin.dense(swin._ln(x.reshape(b, h // 2, w // 2, 4 * c), stage["merge_norm"]),
                      stage["merge"])


def _reference_merge_x0_x2_x1_x3(ref):
    def merge(stage, x, mode="highest"):
        x = jnp.concatenate([x[:, 0::2, 0::2], x[:, 0::2, 1::2],
                             x[:, 1::2, 0::2], x[:, 1::2, 1::2]], -1)
        act = ref._operands(mode)[2]
        return ref._linear(mode, ref._ln(x, stage["merge_norm"], act), stage["merge"])
    return merge


FAULTS = {
    "eps_1e-6": ("LN_EPS", "EPS", lambda ref: 1e-6),
    "tanh_gelu": ("_gelu", "_gelu", lambda ref: _tanh_gelu),
    "merge_x0_x2_x1_x3": ("_merge", "merge", None),
    "mask_-1e9": ("MASKED", "MASK", lambda ref: -1e9),
}


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_reverted_equation_fails_the_comparison(monkeypatch, ref, fault, side):
    """Either side taken back to the old equation, the other kept, is caught."""
    program_name, ref_name, value = FAULTS[fault]
    if side == "program":
        bad = _program_merge_x0_x2_x1_x3 if value is None else value(ref)
        monkeypatch.setattr(swin, program_name, bad)
    else:
        bad = _reference_merge_x0_x2_x1_x3(ref) if value is None else value(ref)
        monkeypatch.setattr(ref, ref_name, bad)
    assert _err(monkeypatch, ref, THREE, sensitive=True) > 5 * TOL


@pytest.mark.parametrize("window,h,shift", [(4, 16, 2), (6, 24, 3), (12, 96, 6), (12, 48, 6)])
def test_window_tables_are_numpy_constants_as_published(ref, window, h, shift):
    """The program holds the relative-position index and the shifted-window
    mask as NumPy constants, equal to the reference's (the authors' code)."""
    index = swin._rel_index(window)
    mask = swin._shift_mask(h, h, window, shift)
    assert isinstance(index, np.ndarray) and isinstance(mask, np.ndarray)
    assert np.array_equal(index, ref._relative_index(window).reshape(-1))
    assert np.array_equal(mask, ref._attn_mask(h, h, window, shift))
    assert set(np.unique(mask)) == {0.0, -100.0}
