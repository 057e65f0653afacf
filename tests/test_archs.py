"""Per-architecture tests on the models' own functions.

The smoke tests run each registered arch's reduced config on CPU (one
forward, prefill, decode step or denoiser call), asserting output shapes and
no NaNs.  ``test_full_config_traces`` traces every arch at its full published
config with ``jax.eval_shape``: init and one forward, nothing allocated."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, list_archs
from repro.models import dit, transformer_lm as lm
from repro.models.common import count_params

LM_ARCHS = ["qwen3-4b", "codeqwen1.5-7b", "moonshot-v1-16b-a3b", "deepseek-v3-671b"]
VISION_ARCHS = ["vit-l16", "swin-b", "convnext-b", "efficientnet-b7"]
DIFFUSION_ARCHS = ["dit-xl2", "unet-sd15"]

# The parameter counts PERF.md section 4 gives for the served configurations:
# in millions to one decimal, and Swin-B at 384 px exactly.
PARAMS_M = {"vgg16": 138.4, "vit-l16": 304.3}
PARAMS_EXACT = {"swin-b-384": 87_903_584}


def _finite(tree) -> bool:
    return all(
        bool(jnp.isfinite(x).all())
        for x in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(x.dtype, jnp.floating)
    )


def _denoiser_inputs(arch, cfg, batch, make):
    """``(latents, t, cond)`` for one denoiser call at the config's latent
    size: DiT takes class labels, the U-Net a text context."""
    r = cfg.latent_res
    lat = make((batch, r, r, cfg.latent_ch), jnp.float32)
    t = make((batch,), jnp.int32)
    if arch.module is dit:
        cond = make((batch,), jnp.int32)
    else:
        cond = make((batch, cfg.ctx_len, cfg.ctx_dim), jnp.float32)
    return lat, t, cond


def _denoiser_out_ch(arch, cfg) -> int:
    return cfg.out_ch if arch.module is dit else cfg.latent_ch


def test_registry_complete():
    archs = list_archs()
    for a in LM_ARCHS + VISION_ARCHS + DIFFUSION_ARCHS + ["vgg16", "swin-b-384"]:
        assert a in archs


@pytest.mark.parametrize("name", list_archs())
def test_full_config_traces(name):
    arch = get(name)
    cfg = arch.cfg
    params = jax.eval_shape(partial(arch.module.init, cfg=cfg), jax.random.PRNGKey(0))
    n = count_params(params)
    assert n > 0
    if name in PARAMS_M:
        assert round(n / 1e6, 1) == PARAMS_M[name], n
    if name in PARAMS_EXACT:
        assert n == PARAMS_EXACT[name]

    sds = jax.ShapeDtypeStruct
    if arch.family == "lm":
        tokens = sds((1, 16), jnp.int32)
        logits, _ = jax.eval_shape(lambda p, x: lm.forward(p, cfg, x), params, tokens)
        assert logits.shape == (1, 16, cfg.vocab)
    elif arch.family == "diffusion":
        lat, t, cond = _denoiser_inputs(arch, cfg, 1, sds)
        eps = jax.eval_shape(lambda p, *a: arch.module.apply(p, cfg, *a), params, lat, t, cond)
        assert eps.shape == lat.shape[:3] + (_denoiser_out_ch(arch, cfg),)
    else:
        x = sds((1, cfg.img_res, cfg.img_res, cfg.in_channels), jnp.float32)
        logits = jax.eval_shape(lambda p, x: arch.module.apply(p, cfg, x), params, x)
        assert logits.shape == (1, cfg.num_classes)


@pytest.mark.parametrize("name", LM_ARCHS)
def test_lm_decode_smoke(name):
    cfg = get(name).smoke_cfg
    params = lm.init(jax.random.PRNGKey(0), cfg)
    cache = lm.init_cache(cfg, 2, 128)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 1), 0, cfg.vocab)
    logits, _ = jax.jit(lambda p, c, x: lm.decode_step(p, cfg, c, x, 0))(params, cache, tokens)
    assert logits.shape == (2, cfg.vocab)
    assert _finite(logits)


@pytest.mark.parametrize("name", LM_ARCHS)
def test_lm_prefill_smoke(name):
    cfg = get(name).smoke_cfg
    params = lm.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab)
    logits, _ = jax.jit(lambda p, x: lm.forward(p, cfg, x))(params, tokens)
    assert logits.shape == (2, 64, cfg.vocab)
    assert _finite(logits)


@pytest.mark.parametrize("name", VISION_ARCHS)
@pytest.mark.parametrize("cell", ["serve_b1"])
def test_vision_smoke(name, cell):
    arch = get(name)
    cfg = arch.smoke_cfg
    params = arch.module.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.img_res, cfg.img_res, 3))
    out = jax.jit(lambda p, x: arch.module.apply(p, cfg, x))(params, x)
    assert out.shape == (1, cfg.num_classes)
    assert _finite(out)


@pytest.mark.parametrize("name", DIFFUSION_ARCHS)
def test_diffusion_gen_smoke(name):
    arch = get(name)
    cfg = arch.smoke_cfg
    params = arch.module.init(jax.random.PRNGKey(0), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 3))

    def make(shape, dtype):
        if dtype == jnp.int32:  # timesteps and labels below every smoke vocabulary
            return jax.random.randint(next(keys), shape, 0, 8)
        return jax.random.normal(next(keys), shape, dtype)

    lat, t, cond = _denoiser_inputs(arch, cfg, 2, make)
    eps = jax.jit(lambda p, *a: arch.module.apply(p, cfg, *a))(params, lat, t, cond)
    assert eps.shape == lat.shape[:3] + (_denoiser_out_ch(arch, cfg),)
    assert _finite(eps)


@pytest.mark.parametrize("name, steps", [
    ("qwen3-4b", 8), ("codeqwen1.5-7b", 8), ("moonshot-v1-16b-a3b", 8),
    ("deepseek-v3-671b", 6),
])
def test_decode_matches_forward(name, steps):
    """Decode with a KV cache (GQA's k/v, MLA's compressed latent) must
    reproduce the teacher-forced forward logits."""
    cfg = get(name).smoke_cfg
    params = lm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, steps), 0, cfg.vocab)
    full_logits, _ = lm.forward(params, cfg, toks)
    cache = lm.init_cache(cfg, 2, 2 * steps)
    for i in range(steps):
        step_logits, cache = lm.decode_step(params, cfg, cache, toks[:, i : i + 1], i)
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(full_logits[:, i]), rtol=2e-4, atol=2e-4
        )
