"""The grouped-query token cell's train step compiled for a described TPU
v5e at the cell's real size (``test_compile_lm_v5e.py``'s way): what the
chip's compiler would refuse (a band-walking kernel it cannot tile, a step
that does not fit 16 GB) is refused here, at no chip time; the plan's bytes
are held against the size floor; no k or v repeated to the query heads'
count is in the step. Nothing runs and nothing here is a measurement. Slow
(two minutes). Not tier-1; run with the other two ``test_compile_*`` files in
one process (``-p no:xdist``), since only one process may load libtpu."""

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


def test_gqa_token_step_compiles_and_fills_the_chip(topo, no_persistent_cache,
                                                    monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.ops import attention
    from analytics_zoo_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    with open(os.path.join(BENCH, "configs", "trinity_mini_ep8.json")) as f:
        cfg = json.load(f)
    traffic = spec.load_json(os.path.join(BENCH, "traffic",
                                          "tokens_packed_16k.json"))
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
               for a in jax.tree.leaves(params)) == 705_473_792

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    ids = jax.ShapeDtypeStruct((batch, seq), jnp.uint16, sharding=one)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    paths = (attention._BACKWARD_FUSED.value,
             attention._BACKWARD_TWO_KERNEL.value)
    compiled = jax.jit(eng._train_step, donate_argnums=(0, 2)).lower(
        sds(params), sds(extra), sds(jax.eval_shape(eng.tx.init, params)),
        step, (ids,), (ids,), None).compile()
    m = compiled.memory_analysis()
    per_chip = m.temp_size_in_bytes + m.argument_size_in_bytes
    print(f"plan: temp {m.temp_size_in_bytes / GIB:.2f} GiB, arguments "
          f"{m.argument_size_in_bytes / GIB:.2f} GiB")
    assert per_chip < HBM, f"{per_chip / GIB:.2f} GiB does not fit a chip"
    assert per_chip >= 0.25 * HBM
    # 8 query heads a kv head at 16384 x 128: dQ is past the fused budget
    assert attention._BACKWARD_FUSED.value == paths[0]
    assert attention._BACKWARD_TWO_KERNEL.value > paths[1]
    text = compiled.as_text()
    # five blocks' flash kernels (forward, dQ, dK/dV) and four expert
    # layers' grouped products
    assert text.count("tpu_custom_call") >= 5 * 3 + 4 * 9
    assert "all-reduce" not in text
    # k and v reach the kernels at their own 4 heads, and nothing has the
    # shape a repeat to 8 query heads a kv head would give them
    assert "bf16[4,16384,128]" in text
    assert "16384,4,8,128" not in text and "4,8,16384,128" not in text
