"""Compile the main path for one TPU v5e chip that is described, not attached.

Every Pallas kernel must lower to a Mosaic ``tpu_custom_call`` (not the
interpreter) and the full-width AlexNet train step must fit the chip.
Nothing runs, so these say nothing about results or times.  The topology is
described inside a fixture, never at import: only one process may load the
TPU compiler's library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ALEXNET
from repro.kernels import flash_attention as fa
from repro.kernels import preprocess as pre
from repro.kernels import quantize as qz
from repro.models import alexnet as A

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("in_hw", [64, 256])
def test_resize_convert_uint8(one_chip, in_hw):
    """Mosaic refuses a kernel whose double-buffered blocks and temporaries
    overflow the scoped VMEM limit (16 MiB on v5e), so the 256x256 compile
    is that check (about 2.2 MB per grid step by hand)."""
    x = _spec(one_chip, (32, in_hw, in_hw, 3), jnp.uint8)
    _compile_kernel(
        lambda x: pre.resize_convert_images(x, 224, 224, interpret=False), x)


def test_normalize_uint8(one_chip):
    x = _spec(one_chip, (32, 3, 224 * 224), jnp.uint8)
    m = _spec(one_chip, (3,), jnp.float32)
    _compile_kernel(
        lambda x, m, s: pre.normalize_images(x, m, s, interpret=False),
        x, m, m)


def test_quantize_and_dequantize_blocks(one_chip):
    n = 4096
    _compile_kernel(lambda x: qz.quantize_blocks(x, interpret=False),
                    _spec(one_chip, (n, qz.BLOCK), jnp.float32))
    _compile_kernel(lambda q, s: qz.dequantize_blocks(q, s, interpret=False),
                    _spec(one_chip, (n, qz.BLOCK), jnp.int8),
                    _spec(one_chip, (n, 1), jnp.float32))


def test_flash_attention(one_chip):
    q = _spec(one_chip, (8, 1024, 128), jnp.bfloat16)
    _compile_kernel(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
        q, q, q)


def test_alexnet_full_width_train_step_fits(one_chip):
    state = jax.eval_shape(lambda: {
        "params": A.init_params(jax.random.PRNGKey(0), ALEXNET),
        "step": jnp.int32(0)})
    state = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), state)
    batch = (_spec(one_chip, (32, ALEXNET.in_hw, ALEXNET.in_hw,
                              ALEXNET.channels), jnp.float32),
             _spec(one_chip, (32,), jnp.int32))
    compiled = A.make_train_step(ALEXNET).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES
