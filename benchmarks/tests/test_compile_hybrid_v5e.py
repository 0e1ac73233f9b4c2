"""The ``nemotron_h`` token cell's train step compiled for a described TPU
v5e at the cell's real size (``test_compile_lm_v5e.py``'s way): what the
chip's compiler would refuse (a grouped product it cannot tile at the latent
shapes, a step that does not fit 16 GB) is refused here, at no chip time; the
plan's bytes are held against the size floor; the scan is the chunked one;
the scatters of scalars on the step's path are the ones whose cost is noted.
Nothing runs and nothing here is a measurement. Slow (two minutes). Not
tier-1; run with the other ``test_compile_*`` files in one process (``-p
no:xdist``), since only one process may load libtpu."""

import json
import os
import re

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


def test_hybrid_token_step_compiles_and_fills_the_chip(
        topo, no_persistent_cache, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.ops import attention, ssm
    from analytics_zoo_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    with open(os.path.join(BENCH, "configs",
                           "nemotron3_super_tp8_ep64.json")) as f:
        cfg = json.load(f)
    traffic = spec.load_json(os.path.join(BENCH, "traffic",
                                          "tokens_packed_8k.json"))
    seq, batch = traffic["sequence_length"], cfg["per_chip_batch"]
    ctx = init_orca_context("local")
    cpu_mesh = mesh_mod.create_mesh({"dp": 1}, devices=ctx.devices[:1])
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    eng = factory.build(cfg, cpu_mesh, batch, 16, seed=0).engine
    one = SingleDeviceSharding(topo.devices[0])
    variables = jax.eval_shape(lambda: eng.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.uint16)))
    params = variables["params"]
    extra = {k: v for k, v in variables.items() if k != "params"}
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(params)) == 700_862_960

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    ids = jax.ShapeDtypeStruct((batch, seq), jnp.uint16, sharding=one)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    sequential = ssm._SEQUENTIAL_ON_TPU.value
    compiled = jax.jit(eng._train_step, donate_argnums=(0, 2)).lower(
        sds(params), sds(extra), sds(jax.eval_shape(eng.tx.init, params)),
        step, (ids,), (ids,), None).compile()
    m = compiled.memory_analysis()
    per_chip = m.temp_size_in_bytes + m.argument_size_in_bytes
    print(f"plan: temp {m.temp_size_in_bytes / GIB:.2f} GiB, arguments "
          f"{m.argument_size_in_bytes / GIB:.2f} GiB, program "
          f"{m.generated_code_size_in_bytes / 2 ** 20:.0f} MiB")
    assert per_chip < HBM, f"{per_chip / GIB:.2f} GiB does not fit a chip"
    assert per_chip >= 0.25 * HBM
    # 8192 positions are 64 whole chunks: no scan position by position
    assert ssm._SEQUENTIAL_ON_TPU.value == sequential
    text = compiled.as_text()
    # one attention block's flash kernels (forward and a fused backward: 4
    # query heads x 8192 x 128 of dQ are 32 MiB) and five expert layers'
    # grouped products (two a layer, forward, rematerialised, and the four
    # of the backward)
    assert text.count("tpu_custom_call") >= 2 + 5 * 8
    assert "all-reduce" not in text
    # k and v reach the kernels at their own one head
    assert "bf16[1,8192,128]" in text
    # the scatters whose updates are scalars (a result of rank 1), by the
    # count of their updates. Every token-choice of a layer (8192 x 22 =
    # 180224) is scattered twice a layer: forward by the jnp.bincount of
    # SparseExperts._after_step (the bias's load and the `load` counter, which
    # XLA makes one), backward by the transpose of the router's
    # take_along_axis (the chosen scores' gradient into the (8192, 512)
    # scores). ~1.6 ms each on a v5e by PERF.md section 5's 1.15 ms for 131072
    # updates, as in the two cells that share the layer. Nothing else
    # scatters as many: the dispatch indexes a chunk's rows
    sizes = {name: int(np.prod([int(d) for d in dims.split(",") if d]))
             for name, dims in re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]",
                                          text)}
    big = [sizes[updates] for updates in re.findall(
        r"= \w+\[\d+\]\S* scatter\(%[\w.\-]+, %[\w.\-]+, %([\w.\-]+)\)",
        text) if sizes.get(updates, 0) >= 8192 * 22]
    assert len(big) == 2 * 5, big
