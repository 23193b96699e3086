"""Each configuration's programs compiled at their real size for a TPU v5e
that is described, not attached, through its architecture's module: the
program's train step on the chips its cells take, and the reference's step.
Nothing runs; this is what the chip's compiler would refuse.  The topology
is described inside a fixture, never at import."""
import os

import jax
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from bench.spec import Spec

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
    spec = Spec()
    cfg = spec.config(config)
    model = cfg["model"]
    arch = spec.arch(model["arch"])
    mesh = jax.sharding.Mesh(topo.devices[:chips], ("data",),
                             axis_types=(AxisType.Auto,))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    state = jax.eval_shape(
        lambda: arch.make_state(0, model, jax.devices()[:1]))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), state)
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rows),
        arch.batch_shapes(cfg))
    return arch, model, state, batch


# each configuration on the chips its cells take
CONFIGS = sorted({(w["config"], w["chips"]) for w in Spec().data["workloads"]})


@pytest.mark.parametrize("config,chips", CONFIGS)
def test_program_step_compiles(topo, config, chips):
    arch, model, state, batch = shapes(config, chips, topo)
    step = arch.make_train_step(model, topo.devices[:chips])
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("config,chips", CONFIGS)
def test_reference_step_compiles_on_one_chip(topo, config, chips):
    arch, model, state, batch = shapes(config, chips, topo)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    put = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)  # noqa: E731
    arch.lower_reference(jax.tree.map(put, state["params"]),
                         jax.tree.map(put, batch), model).compile()
