"""Conv and dense kernels kept in bfloat16 from ``build_model`` on, and the
layers that take them as kept: f32 activations rounded to the kernel's type,
f32 accumulation, no weight converted on the call."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.launch.serve import build_model
from repro.models.layers import conv2d, dense
from repro.runtime.tracing import SpanLog

CONV_DIMS = ("NHWC", "HWIO", "NHWC")


def _key(path) -> str | None:
    return getattr(path[-1], "key", None)


@pytest.mark.parametrize("arch", ["vgg16", "vit-l16", "swin-b-384"])
def test_kernels_are_kept_in_bf16_and_the_rest_in_f32(arch):
    log = SpanLog()
    _cfg, params, _fn = build_model(arch, smoke=True, spans=log)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    kernels = [a for path, a in leaves if _key(path) == "w"]
    others = [a for path, a in leaves if _key(path) != "w"]
    assert kernels and others
    assert {a.dtype for a in kernels} == {jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in others} == {jnp.dtype(jnp.float32)}
    (init,) = [s for s in log.spans if s.name == "build.init"]
    assert init.info["operand_bytes"] == sum(a.nbytes for a in kernels)
    assert init.info["weight_bytes"] == sum(a.nbytes for _, a in leaves)
    assert 0 < init.info["operand_bytes"] <= init.info["weight_bytes"]


def _inputs(dtype):
    kx, kw, kc, kb = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(kx, (2, 7, 48))
    dense_p = {"w": jax.random.normal(kw, (48, 24)).astype(dtype),
               "b": jax.random.normal(kb, (24,))}
    img = x.reshape(2, 7, 6, 8)
    conv_p = {"w": jax.random.normal(kc, (3, 3, 8, 16)).astype(dtype),
              "b": jax.random.normal(kb, (16,))}
    return x, dense_p, img, conv_p


def _same_bits(a, b):
    assert a.dtype == b.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_kernels_take_f32_activations_rounded_to_bf16():
    x, dp, img, cp = _inputs(jnp.bfloat16)
    want = lax.dot_general(x.astype(jnp.bfloat16), dp["w"], (((2,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32) + dp["b"]
    _same_bits(jax.jit(dense)(x, dp), want)
    want = lax.conv_general_dilated(img.astype(jnp.bfloat16), cp["w"], (1, 1), "SAME",
                                    dimension_numbers=CONV_DIMS,
                                    preferred_element_type=jnp.float32) + cp["b"]
    _same_bits(jax.jit(conv2d)(img, cp), want)


def test_f32_kernels_are_computed_as_before():
    x, dp, img, cp = _inputs(jnp.float32)
    _same_bits(jax.jit(dense)(x, dp), jax.jit(lambda x, p: x @ p["w"] + p["b"])(x, dp))
    want = jax.jit(lambda x, p: lax.conv_general_dilated(
        x, p["w"], (2, 2), [(1, 1), (1, 1)], dimension_numbers=CONV_DIMS,
        preferred_element_type=jnp.float32) + p["b"])(img, cp)
    _same_bits(jax.jit(lambda x, p: conv2d(x, p, stride=2, padding=1))(img, cp), want)


_TYPES = r"\(tensor<([^>]*)>, tensor<([^>]*)>\) ->"


def _elem(tensor_type: str) -> str:
    return tensor_type.rsplit("x", 1)[-1]


@pytest.mark.parametrize("arch, f32_products", [
    ("vgg16", 0),
    ("vit-l16", 2),  # attention's scores and values multiply activations, which stay f32
])
def test_served_program_converts_no_weight_on_the_call(arch, f32_products):
    """In the served function's lowered program no convert starts from a bf16
    value (the kernels are the only ones), and every conv or dot takes two
    operands of one type: bf16 where one is a kernel."""
    cfg, params, fn = build_model(arch, smoke=True)
    x = jax.ShapeDtypeStruct((2, cfg.img_res, cfg.img_res, cfg.in_channels), jnp.float32)
    text = fn.func.lower(params, x).as_text()
    assert not re.findall(r"stablehlo\.convert [^\n]*: \(tensor<[^>]*bf16>\)", text)
    products = [(_elem(a), _elem(b)) for a, b in re.findall(
        r"stablehlo\.(?:dot_general|convolution)[^\n]*: " + _TYPES, text)]
    assert products
    assert set(products) <= {("bf16", "bf16"), ("f32", "f32")}, set(products)
    assert products.count(("f32", "f32")) == f32_products
