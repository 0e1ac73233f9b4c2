"""CPU rehearsals of the block-diffusion token cells' driver
(``harness/bd_lm_fit_cell.py``): the real ``TPUEstimator.fit`` on arrays of
ids at a toy size, the result line, `correct` coming out false for the
control, for faults under the timed path and for the faults of this
mechanism; ``work_bd.py`` pinned to a count by hand; the new readers on
hand-made facts. Not part of tier-1."""

import json
import os
import time
import types

import pytest

import tampers
from harness import bd_lm_fit_cell, check, fit_cell, runner, spec, work_bd, \
    work_gqa

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(BENCH, "tests", "data", "toy_bd")
SEED = 2_147_483_659


def toy_cell():
    return spec.load_cell("toy_bd.fit", os.path.join(TOY, "BENCHMARK.json"),
                          [BENCH, TOY])


def run_toy(tmp_path, traced=False, tamper=None):
    cell = toy_cell()
    out = bd_lm_fit_cell.run(cell, SEED, 0.5, traced, time.perf_counter(),
                             cluster_mode="local",
                             scratch=str(tmp_path / "run"), tamper=tamper)
    line, code = runner.assemble(cell, out, traced)
    assert code == 0
    json.dumps(line)
    return line, out


def test_bd_token_cell_end_to_end(tmp_path):
    line, out = run_toy(tmp_path)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s_per_chip"}
    w = out["facts"]["window"]
    assert w["epochs"] >= 1 and w["samples"] == w["epochs"] * 16
    assert w["compiles_in_window"] == 0
    assert line["compared"]["moe_dropped_rows"]["value"] == 0
    # both copies route: 2 x 64 positions x 4 choices x 4 held / 32 a layer
    assert out["facts"]["moe"]["moe_local_rows"] > 0
    assert out["facts"]["model_config"]["noise_seed"] == SEED % (2 ** 31 - 1)
    entries = out["facts"]["bd_entries"]
    assert entries["computed"] >= entries["needed"] > 0
    assert not os.path.exists(tmp_path / "run")


def test_traced_run_reports_what_it_can_read(tmp_path):
    line, out = run_toy(tmp_path, traced=True)
    # no device plane on the CPU: the device trace's readers find nothing
    # and are left out; the counters' readers report
    assert {"moe_rows_max_over_mean", "compile_s", "infeed_stall_pct.train",
            "bd_score_entries_computed_over_needed"} <= set(line["metrics"])
    assert line["metrics"]["bd_score_entries_computed_over_needed"][
        "value"] >= 1.0
    assert not {"bd_attention_roofline", "bd_dense_dot_roofline",
                "gqa_expert_gmm_roofline", "mfu.train"} & set(line["metrics"])


def other_noise(prep):
    """The program draws another seed's noise than the reference's."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import \
        NOISE_STATE
    eng = prep.est.engine
    noise = dict(eng.extra_vars[NOISE_STATE])
    noise["seed"] = noise["seed"] + 1
    eng.extra_vars = dict(eng.extra_vars, **{NOISE_STATE: noise})


@pytest.mark.parametrize("tamper,caught_by", [
    (tampers.half_batch, "grad_diff_median"),
    (other_noise, "grad_diff_median"),
    (tampers.state_unchanged, "dparam_diff_median"),
])
def test_a_fault_under_the_timed_path_is_not_correct(tmp_path, tamper,
                                                     caught_by):
    line, _ = run_toy(tmp_path, tamper=tamper)
    assert line["correct"] is False
    row = line["compared"][caught_by]
    assert row["value"] > row["limit"]


def test_the_control_and_the_mechanisms_faults_are_not_correct():
    from reference import nn
    cell = toy_cell()
    mesh, devices = fit_cell.open_context(cell, "local")
    prep = bd_lm_fit_cell.prepare(cell, SEED, mesh, devices)
    bd_lm_fit_cell.first_steps(prep)
    fit_cell.free_program(prep)
    reference = cell.load("reference")
    ref = bd_lm_fit_cell.reference_readings(prep)
    sides = {"control": bd_lm_fit_cell.reference_readings(
        prep, quant=nn.fp8_quant)}
    for fault in bd_lm_fit_cell.FAULTS:
        sides[fault] = bd_lm_fit_cell.reference_readings(prep, fault=fault)
    for name, side in sides.items():
        numbers = bd_lm_fit_cell.compare_sides(side, ref, prep.shapes,
                                               reference)
        limits = {k: v for k, v in cell.limits.items() if k in numbers}
        correct, table = check.verdict(numbers, limits)
        assert correct is False, (name, table)
    with pytest.raises(ValueError):
        bd_lm_fit_cell.reference_readings(prep, fault="no_such_fault")


@pytest.fixture(scope="module")
def model_cfg():
    cfg = spec.load_json(os.path.join(BENCH, "configs",
                                      "sdar_30b_a3b_ep8.json"))
    return spec.load_py(os.path.join(BENCH, cfg["factory"])).model_config(cfg)


def test_work_bd_is_the_hand_count(model_cfg):
    m = work_bd.matrices(model_cfg)
    assert m["attention"] == 18_874_368            # q, k, v, o: 18.87 M
    assert m["router"] == 262_144 and m["expert"] == 4_718_592
    assert work_bd.param_count(model_cfg) == 645_625_344        # 10.33 GB
    assert work_bd.score_entries(8192, 4) == 67_141_632         # 67.14 M
    att = work_bd.attention_flops_per_sequence(model_cfg, 8192)
    assert 6 * (att["fwd"] + att["bwd"]) == pytest.approx(19.8e12,
                                                          rel=0.005)
    dense = 6.0 * (work_bd.layer_params_per_position(model_cfg) * 16384
                   + m["head"] * 8192)
    assert dense == pytest.approx(13.2e12, rel=0.005)
    # a step at balance: 16384 rows a layer to the 16 held experts
    step = work_bd.train_flops_per_sample(model_cfg, 8192, 6 * 16384)
    assert step - dense - 6 * (att["fwd"] + att["bwd"]) == pytest.approx(
        2.8e12, rel=0.01)
    assert step == pytest.approx(35.8e12, rel=0.005)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_work_bd_least_times_and_the_readers(model_cfg):
    att = work_bd.attention_min_seconds(model_cfg, 8192, 1, 2, PEAKS)
    assert att == pytest.approx(19.8e12 / 197e12, rel=0.005)     # 100 ms
    dense = work_bd.dense_min_seconds(model_cfg, 8192, 1, 2, PEAKS)
    assert dense == pytest.approx(13.2e12 / 197e12, rel=0.005)   # 67 ms
    # the grouped-query family's count: every layer an expert layer here
    experts = work_gqa.expert_min_seconds(model_cfg, 6 * 16384, 2, PEAKS)
    assert experts == pytest.approx(3 * max(
        2 * 4_718_592 * 6 * 16384 / 197e12,
        (6 * 16 * 4_718_592 + 6 * 16384 * (2 * 2048 + 3 * 768)) * 2 / 819e9))
    cell = toy_cell()
    facts = {"model_config": model_cfg, "sequence_length": 8192,
             "global_batch": 1, "chips": 1, "dtype_bytes": 2,
             "moe": {"moe_local_rows": 6 * 16384},
             "bd_entries": {"computed": 30.0, "needed": 25.0},
             "scope_seconds": {"all": 8 * 0.6, "attn.bd": 8 * 0.2,
                               "moe.experts": 8 * 0.1}}
    ctx = {"facts": facts, "peaks": PEAKS,
           "trace": types.SimpleNamespace(steps=8, matmul_s=8 * 0.1)}
    read = cell.metric_reader
    assert read("bd_attention_roofline")(ctx) == pytest.approx(
        100 * att / 0.2)
    assert read("bd_dense_dot_roofline")(ctx) == pytest.approx(
        100 * dense / 0.1)
    assert read("gqa_expert_gmm_roofline")(ctx) == pytest.approx(
        100 * experts / 0.1)
    assert read("bd_score_entries_computed_over_needed")(ctx) == 1.2
    # a program without the scopes or the counter (the parent): nothing to
    # read, no error
    for name in ("bd_attention_roofline", "bd_dense_dot_roofline",
                 "bd_score_entries_computed_over_needed"):
        assert read(name)({"facts": {}, "trace": None, "peaks": None}) is None
        assert read(name)({"facts": {
            "scope_seconds": {"all": 1.0, "attn.mla": 0.5},
            "bd_entries": {"computed": 0.0, "needed": 0.0}},
            "trace": types.SimpleNamespace(steps=8, matmul_s=0.0),
            "peaks": PEAKS}) is None


# --- the real step, compiled for a described v5e ------------------------------

GIB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_bd_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """The cell's train step at its own size (one 8192-token sequence as
    16384 positions, 645.6 M parameters) compiled for one chip of a
    described v5e: it fits 16 GB and fills more than a quarter of it; every
    block's attention reaches the flash kernels (forward and one fused
    backward), none falls to the reference. Nothing runs: no number here is
    a measurement."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.ops import attention
    from analytics_zoo_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    fell = attention._REFERENCE_ON_TPU.value
    try:
        cfg = spec.load_json(os.path.join(BENCH, "configs",
                                          "sdar_30b_a3b_ep8.json"))
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
                   for a in jax.tree.leaves(params)) == 645_625_344

        def sds(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one), tree)

        ids = jax.ShapeDtypeStruct((batch, seq), jnp.uint16, sharding=one)
        step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        compiled = jax.jit(eng._train_step, donate_argnums=(0, 2)).lower(
            sds(params), sds(extra), sds(jax.eval_shape(eng.tx.init, params)),
            step, (ids,), (ids,), None).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    m = compiled.memory_analysis()
    per_chip = m.temp_size_in_bytes + m.argument_size_in_bytes
    print(f"plan: temp {m.temp_size_in_bytes / GIB:.2f} GiB, arguments "
          f"{m.argument_size_in_bytes / GIB:.2f} GiB, program "
          f"{m.generated_code_size_in_bytes / 2 ** 20:.0f} MiB")
    assert per_chip < 16 * GIB, f"{per_chip / GIB:.2f} GiB does not fit"
    assert per_chip >= 0.25 * 16 * GIB
    assert attention._REFERENCE_ON_TPU.value == fell
    assert compiled.as_text().count("tpu_custom_call") >= 2 * 6


def test_every_reader_of_the_cell_reads_the_drivers_scopes(model_cfg):
    """A traced run's facts as this driver makes them (a scope table over
    its own ``SCOPES``): every per-layer metric the cell reports reads them
    without an error; the share readers written for other families find the
    scopes they add up (``moe.shared`` reads 0: no shared expert)."""
    from harness import trace
    cell = spec.load_cell("sdar_30b_a3b.fit.bd8k")
    by = {n: 0.01 for n in bd_lm_fit_cell.SCOPES}
    by.update({f"{n}:kernels": 0.005 for n in bd_lm_fit_cell.SCOPES})
    by.update({"moe.shared": 0.0, "all": 4.0})
    reduction = trace.TraceReduction(
        chips=1, window_s=4.2, busy_s=4.1, busy_s_least=4.1, steps=8,
        step_program="train", step_intervals_s=[0.5] * 7, matmul_s=1.0,
        collective_s=0.0, collective_exposed_s=0.0)
    facts = {"chips": 1, "global_batch": 1, "sequence_length": 8192,
             "train_flops_per_sample": 35.8e12, "dtype_bytes": 2,
             "model_config": model_cfg, "scope_seconds": by,
             "bd_entries": {"computed": 1.2, "needed": 1.0},
             "moe": {"moe_local_rows": 98304.0,
                     "moe_rows_max_over_mean": 3.0,
                     "moe_rows_moved_over_routed": 1.1},
             "compile": {"setup_compile_s": 0.0}}
    ctx = {"trace": reduction, "facts": facts, "cell": cell, "peaks": PEAKS}
    read = {}
    for m in cell.per_layer:
        if m["name"].startswith("infeed_"):
            continue                 # the window's pump counters, not these
        read[m["name"]] = cell.metric_reader(m["name"])(ctx)
    assert read["moe_share_pct.train"] == pytest.approx(100 * 0.02 / 4.0)
    assert read["bd_score_entries_computed_over_needed"] == 1.2
