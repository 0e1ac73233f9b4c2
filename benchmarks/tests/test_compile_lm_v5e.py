"""The token cell's train step compiled for a described TPU v5e at the cell's
real size: what the chip's compiler would refuse (a kernel it cannot tile, a
step that does not fit 16 GB) is refused here, at no chip time, and the bytes
it plans are held against the size floor. Nothing runs and nothing here is a
measurement. The topology is described in a fixture, never at import. Slow
(a minute and a half). Not tier-1. A file of its own beside
``test_compile_v5e.py``: run the two in one process (``-p no:xdist``), since
only one process at a time may load libtpu."""

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


def test_token_step_compiles_and_fills_the_chip(topo, no_persistent_cache,
                                                monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.ops import attention
    from analytics_zoo_tpu.parallel import mesh as mesh_mod
    # the program asks the backend which branch to take and sees the CPU:
    # steer the kernels (flash and grouped matmul) to their compiled form
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    with open(os.path.join(BENCH, "configs",
                           "joyai_llm_flash_ep16.json")) as f:
        cfg = json.load(f)
    traffic = spec.load_json(os.path.join(BENCH, "traffic",
                                          "tokens_packed_8k.json"))
    seq, batch = traffic["sequence_length"], cfg["per_chip_batch"]
    ctx = init_orca_context("local")
    cpu_mesh = mesh_mod.create_mesh({"dp": 1}, devices=ctx.devices[:1])
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    eng = factory.build(cfg, cpu_mesh, batch, 8, seed=0).engine
    one = SingleDeviceSharding(topo.devices[0])
    variables = jax.eval_shape(lambda: eng.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.uint16)))
    params = variables["params"]
    extra = {k: v for k, v in variables.items() if k != "params"}
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(params)) == 680_439_808

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    ids = jax.ShapeDtypeStruct((batch, seq), jnp.uint16, sharding=one)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    compiled = jax.jit(eng._train_step, donate_argnums=(0, 2)).lower(
        sds(params), sds(extra), sds(jax.eval_shape(eng.tx.init, params)),
        step, (ids,), (ids,), None).compile()
    m = compiled.memory_analysis()
    per_chip = m.temp_size_in_bytes + m.argument_size_in_bytes
    assert per_chip < HBM, f"{per_chip / GIB:.2f} GiB does not fit a chip"
    assert per_chip >= 0.25 * HBM
    text = compiled.as_text()
    # six blocks' flash kernels (forward, rematerialised forward, dQ,
    # dK/dV) and five expert layers' grouped products
    assert text.count("tpu_custom_call") >= 6 * 4 + 5 * 9
    assert "all-reduce" not in text
