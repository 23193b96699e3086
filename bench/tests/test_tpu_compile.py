"""Each cell's programs compiled at their real size for a TPU v5e that is
described, not attached: the program's train step on the chips its cell
takes, and the reference's step.
Nothing runs; this is what the chip's compiler would refuse.  The topology
is described inside a fixture, never at import."""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from bench import reference, weights
from bench.loop import program_config
from bench.spec import Spec
from repro.models import alexnet as A

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def shapes(config, chips, topo):
    cfg = json.loads((Spec().home / "configs" / f"{config}.json").read_text())
    mesh = jax.sharding.Mesh(topo.devices[:chips], ("data",),
                             axis_types=(AxisType.Auto,))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    model = cfg["model"]
    params = jax.eval_shape(lambda: weights._init(
        jnp.zeros(2, jnp.uint32), {k: tuple(v) if isinstance(v, list) else v
                                   for k, v in model.items()}))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), params)
    hw = model["in_hw"]
    images = jax.ShapeDtypeStruct((cfg["batch"], hw, hw, model["channels"]),
                                  jnp.float32, sharding=rows)
    labels = jax.ShapeDtypeStruct((cfg["batch"],), jnp.int32, sharding=rows)
    return cfg, model, params, images, labels, rep


# each configuration on the chips its cell takes
CONFIGS = [("alexnet_caltech101", 1)]


@pytest.mark.parametrize("config,chips", CONFIGS)
def test_program_step_compiles(topo, config, chips):
    cfg, model, params, images, labels, rep = shapes(config, chips, topo)
    step = A.make_train_step(program_config(model))
    state = {"params": params,
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
    compiled = step.lower(state, (images, labels)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("config,chips", CONFIGS)
def test_reference_step_compiles_on_one_chip(topo, config, chips):
    cfg, model, params, images, labels, _ = shapes(config, chips, topo)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    put = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)  # noqa: E731
    key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                       for k, v in model.items()))
    with jax.default_matmul_precision("highest"):
        reference._step.lower(
            jax.tree.map(put, params), put(images), put(labels),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=one),
            model_key=key, fp8=False, rows=None).compile()
