"""Compile the main-path Pallas conv kernels for a described TPU v5e at
VGG-16's real widths (224 px), with no chip attached.

Interpret-mode tests cannot see what the chip's compiler refuses: a block
layout or a scoped-VMEM working set over Mosaic's 16 MiB limit.  These
compiles can.  The topology is described inside a fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test workers import every test file."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.conv2d import conv2d_pallas
from repro.kernels.halo_conv import halo_conv2d


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # the TPU library otherwise writes its driver logs under /tmp, outside
    # the checkout; it reads this once, when the topology loads it
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep it out of any configured cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_count(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize(
    "rows,cin,cout,dtype",
    [
        (224, 3, 64, jnp.float32),  # conv1_1: 3 channels pad to 128 lanes
        (224, 64, 64, jnp.float32),  # conv1_2
        (56, 128, 256, jnp.float32),  # conv3_1
        (14, 512, 512, jnp.float32),  # conv5_1
        (224, 3, 64, jnp.bfloat16),  # bf16 blocks: 16-row VMEM tiles
    ],
    ids=["conv1_1", "conv1_2", "conv3_1", "conv5_1", "conv1_1_bf16"],
)
def test_conv2d_pallas_compiles_at_vgg16_widths(one_chip, rows, cin, cout, dtype):
    fn = jax.jit(lambda x, w: conv2d_pallas(x, w, padding=1))
    compiled = fn.lower(
        _spec((1, rows, rows, cin), one_chip, dtype),
        _spec((3, 3, cin, cout), one_chip, dtype),
    ).compile()
    assert _kernel_count(compiled) == 1


@pytest.mark.parametrize(
    "rows,cin,cout",
    [
        (64, 3, 64),  # a full shard of (64, 64, 32, 64) at conv1_1
        (64, 64, 64),  # ... and at conv1_2
        (65, 64, 64),  # the weighted layout's block plus its bottom pad row
        (32, 64, 64),  # the short shard
    ],
    ids=["64x224_c3", "64x224_c64", "65x224_c64", "32x224_c64"],
)
def test_halo_conv2d_compiles_at_four_chip_shards(one_chip, rows, cin, cout):
    w = 224
    fn = jax.jit(lambda x, t, b, wt: halo_conv2d(x, t, b, wt, padding=1))
    compiled = fn.lower(
        _spec((1, rows, w, cin), one_chip),
        _spec((1, 1, w, cin), one_chip),
        _spec((1, 1, w, cin), one_chip),
        _spec((3, 3, cin, cout), one_chip),
    ).compile()
    assert _kernel_count(compiled) == 1
