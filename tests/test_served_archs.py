"""Every servable arch, served: the launcher's function behind the batching
engine gives, row by row, the arch's own forward on each image alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.launch.serve import build_model, serve

SERVED_ARCHS = ["vgg16", "vit-l16", "swin-b", "swin-b-384", "convnext-b", "efficientnet-b7"]
WIDTH = 4  # the engine's max_batch: every batch runs padded to it


@pytest.mark.parametrize("requests", [1, 3])
@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_served_rows_match_the_plain_forward(arch, requests):
    """One batch of ``requests`` padded to the engine's width of 4: each
    request's row equals the plain forward, with the served params, of its
    image alone, repeated to that width (VGG-16's is ``vgg.apply``, without
    the HALP plan).  The plain forward runs at the executed width because
    with bf16 kernels a logit moves with the batch width on its own: f32
    accumulation order changes by an ulp and the next layer's rounding to
    bf16 can carry it (Swin-B: up to 8e-5 between widths 1 and 4)."""
    cfg, params, fn = build_model(arch, smoke=True)
    plain = get(arch).module.apply
    forward = jax.jit(lambda p, x: plain(p, cfg, x))
    eng = serve(fn, cfg, requests=requests, max_batch=WIDTH, deadline_s=60.0, seed=3)
    assert len(eng.completed) == requests
    for r in eng.completed:
        alone = jnp.repeat(r.payload[None], WIDTH, axis=0)
        np.testing.assert_allclose(np.asarray(r.result), np.asarray(forward(params, alone)[0]),
                                   rtol=1e-5, atol=1e-5)
