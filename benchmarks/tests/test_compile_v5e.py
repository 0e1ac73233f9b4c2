"""Both train steps compiled for a described TPU v5e at the cells' real sizes:
what the chip's compiler would refuse (a shape it cannot tile, a step that
does not fit 16 GB) is refused here, at no chip time, and the bytes the
compiler plans are held against the driver's size floor.

Nothing runs and nothing here is a measurement. The topology is described in
a fixture, never at import (one process at a time may load libtpu; see the
on-chip-measurement guide). Slow: a minute or two a compile. Not tier-1.
"""

import json
import os

import numpy as np
import pytest

from harness import spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30
HBM = 16 * GIB


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def compile_step(config_name, chips, topo):
    """The engine's own step function, lowered on shapes that carry the
    described chips' shardings (a scratch hand-over: the estimator itself
    builds its mesh from the devices that are attached)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    with open(os.path.join(BENCH, "configs", f"{config_name}.json")) as f:
        cfg = json.load(f)
    ctx = init_orca_context("local")
    cpu_mesh = create_mesh({"dp": 1}, devices=ctx.devices[:1])
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    batch = cfg["per_chip_batch"] * chips
    est = factory.build(cfg, cpu_mesh, batch, 32, seed=0)
    eng = est.engine
    size = cfg["image_size"]
    eng.build((np.zeros((1, size, size, 3), np.uint8),))
    mesh = Mesh(np.array(topo.devices[:chips]), ("dp",))
    repl = NamedSharding(mesh, P())

    def sds(tree, sharding=repl):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    x = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.uint8,
                             sharding=NamedSharding(mesh, P("dp")))
    y = jax.ShapeDtypeStruct((batch,), jnp.int32,
                             sharding=NamedSharding(mesh, P("dp")))
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=repl)
    lowered = jax.jit(eng._train_step, donate_argnums=(0, 2)).lower(
        sds(eng.params), sds(eng.extra_vars), sds(eng.opt_state), step,
        (x,), (y,), None)
    return lowered.compile()


@pytest.mark.parametrize("config_name,chips,floor_share", [
    ("resnet50_imagenet", 1, 0.25),
    ("inception_v1_imagenet", 1, 0.25),
    ("resnet50_imagenet", 4, 0.25),
])
def test_train_step_compiles_and_fills_the_chip(topo, no_persistent_cache,
                                                config_name, chips,
                                                floor_share):
    compiled = compile_step(config_name, chips, topo)
    m = compiled.memory_analysis()
    per_chip = m.temp_size_in_bytes + m.argument_size_in_bytes
    assert per_chip < HBM, f"{per_chip / GIB:.2f} GiB does not fit a chip"
    assert per_chip >= floor_share * HBM, \
        f"{per_chip / GIB:.2f} GiB a chip is under the size floor"
    text = compiled.as_text()
    assert "convolution" in text
    assert ("all-reduce" in text) == (chips > 1)
