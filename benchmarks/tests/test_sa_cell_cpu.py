"""CPU rehearsals of the learned-sparse-attention token cells' driver
(``harness/sa_lm_fit_cell.py``): the real ``TPUEstimator.fit`` on arrays of
ids at a toy size, the result line, `correct` coming out false for the
control, for faults under the timed path and for the faults of this
mechanism planted in the reference; ``work_sa.py`` pinned to a count by
hand; the new readers on hand-made facts; the cell's real step compiled for
a described v5e. Not part of tier-1."""

import json
import os
import time
import types

import pytest

import tampers
from harness import check, fit_cell, runner, sa_lm_fit_cell, spec, work_sa

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(BENCH, "tests", "data", "toy_sa")
SEED = 2_147_483_659


def toy_cell():
    return spec.load_cell("toy_sa.fit", os.path.join(TOY, "BENCHMARK.json"),
                          [BENCH, TOY])


def run_toy(tmp_path, traced=False, tamper=None):
    cell = toy_cell()
    out = sa_lm_fit_cell.run(cell, SEED, 0.5, traced, time.perf_counter(),
                             cluster_mode="local",
                             scratch=str(tmp_path / "run"), tamper=tamper)
    line, code = runner.assemble(cell, out, traced)
    assert code == 0
    json.dumps(line)
    return line, out


def test_sa_token_cell_end_to_end(tmp_path):
    line, out = run_toy(tmp_path)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s_per_chip"}
    w = out["facts"]["window"]
    assert w["epochs"] >= 1 and w["samples"] == w["epochs"] * 16
    assert w["compiles_in_window"] == 0
    assert line["compared"]["moe_dropped_rows"]["value"] == 0
    entries = out["facts"]["sa_entries"]
    assert entries["computed"] >= entries["needed"] > 0
    # the losses compared apart: the LM's and the indexer's add up to it
    program = out["facts"]["program_readings"]
    for total, lm, l_i in zip(program["losses"], program["lm_losses"],
                              program["indexer_losses"]):
        assert total == pytest.approx(lm + l_i) and 0 < l_i < lm
    assert not os.path.exists(tmp_path / "run")


def test_traced_run_reports_what_it_can_read(tmp_path):
    line, out = run_toy(tmp_path, traced=True)
    # no device plane on the CPU: the device trace's readers find nothing
    # and are left out; the counters' readers report
    assert {"moe_rows_max_over_mean", "compile_s", "infeed_stall_pct.train",
            "sa_entries_computed_over_needed"} <= set(line["metrics"])
    assert line["metrics"]["sa_entries_computed_over_needed"]["value"] >= 1
    assert not {"sa_attention_roofline", "sa_indexer_roofline",
                "sa_indexer_share_pct.train", "sa_dense_dot_roofline",
                "gqa_expert_gmm_roofline", "mfu.train"} & set(line["metrics"])


@pytest.mark.parametrize("tamper,caught_by", [
    (tampers.half_batch, "grad_diff_median"),
    (tampers.state_unchanged, "dparam_diff_median"),
])
def test_a_fault_under_the_timed_path_is_not_correct(tmp_path, tamper,
                                                     caught_by):
    line, _ = run_toy(tmp_path, tamper=tamper)
    assert line["correct"] is False
    row = line["compared"][caught_by]
    assert row["value"] > row["limit"]


@pytest.fixture(scope="module")
def readings():
    """One seed's sides: the program's first steps, the reference, the fp8
    control and each of the mechanism's faults planted in the reference,
    each compared with the reference."""
    from reference import nn
    cell = toy_cell()
    mesh, devices = fit_cell.open_context(cell, "local")
    prep = sa_lm_fit_cell.prepare(cell, SEED, mesh, devices)
    sa_lm_fit_cell.first_steps(prep)
    fit_cell.free_program(prep)
    reference = cell.load("reference")
    ref = sa_lm_fit_cell.reference_readings(prep)
    sides = {"program": prep.program,
             "control": sa_lm_fit_cell.reference_readings(
                 prep, quant=nn.fp8_quant)}
    for fault in sa_lm_fit_cell.FAULTS:
        sides[fault] = sa_lm_fit_cell.reference_readings(prep, fault=fault)
    with pytest.raises(ValueError):
        sa_lm_fit_cell.reference_readings(prep, fault="no_such_fault")
    return cell, {name: sa_lm_fit_cell.compare_sides(
        side, ref, prep.shapes, reference) for name, side in sides.items()}


def test_the_program_passes_and_the_control_and_faults_do_not(readings):
    cell, numbers = readings
    for name, n in numbers.items():
        limits = {k: v for k, v in cell.limits.items() if k in n}
        correct, table = check.verdict(n, limits)
        assert correct is (name == "program"), (name, table)


@pytest.mark.parametrize("fault,caught_by", [
    ("dense_attention", "sa_selection_diff_share"),
    ("no_causal_clip", "sa_selection_diff_share"),
    ("indexer_loss_dropped", "indexer_loss_gap_1"),
    ("kl_target_head0", "indexer_loss_gap_1")])
def test_each_fault_is_caught_where_the_mechanism_shows(readings, fault,
                                                        caught_by):
    """A selection that is not the indexer's top-k shows in the selected
    pairs; a loss or a target that is not DSA's shows in L_I, while the LM
    loss and every gradient but the indexer's stay the reference's."""
    cell, numbers = readings
    assert numbers[fault][caught_by] > cell.limits[caught_by]
    if caught_by.startswith("indexer"):
        assert numbers[fault]["lm_loss_gap_1"] == 0.0
        assert numbers[fault]["sa_selection_diff_share"] == 0.0


@pytest.fixture(scope="module")
def model_cfg():
    cfg = spec.load_json(os.path.join(BENCH, "configs",
                                      "keye_vl2_30b_a3b_ep8.json"))
    return spec.load_py(os.path.join(BENCH, cfg["factory"])).model_config(cfg)


def test_work_sa_is_the_hand_count(model_cfg):
    m = work_sa.matrices(model_cfg)
    assert m["attention"] == 18_874_368 and m["indexer"] == 2_260_992
    assert m["router"] == 262_144 and m["expert"] == 4_718_592
    assert work_sa.param_count(model_cfg) == 659_190_016        # 10.55 GB
    # 2048 * 2049 / 2 + (16384 - 2048) * 2048 = 31.46 M a head
    assert work_sa.selected_entries(16384, 2048) == 31_458_304
    assert work_sa.causal_entries(16384) == 134_225_920
    mech = work_sa.mechanism_flops_per_sequence(model_cfg, 16384)
    # 515 GF forward, 1031 GF backward; the indexer 275 + 129 + 258 GF
    assert mech["attention"] == pytest.approx(1546e9, rel=0.001)
    assert mech["indexer"] == pytest.approx(662e9, rel=0.002)
    step = work_sa.train_flops_per_sample(model_cfg, 16384, 6 * 16384)
    dense = (6.0 * (18_874_368 + 262_144) + 4.0 * 2_260_992) * 6 * 16384 \
        + 6.0 * 2048 * 18992 * 16384 + 6.0 * 4_718_592 * 6 * 16384
    assert step == pytest.approx(dense + 6 * (1546e9 + 662e9), rel=0.002)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_work_sa_least_times_and_the_readers(model_cfg):
    att = work_sa.attention_min_seconds(model_cfg, 16384, 1, 2, PEAKS)
    assert att == pytest.approx(6 * 1546e9 / 197e12, rel=0.001)   # 47 ms
    idx = work_sa.indexer_min_seconds(model_cfg, 16384, 1, 2, PEAKS)
    assert idx == pytest.approx(6 * 662e9 / 197e12, rel=0.002)    # 20 ms
    dense = work_sa.dense_min_seconds(model_cfg, 16384, 2, PEAKS)
    # q/k/v/o and the routers of six layers, the head: 6 p t, 76.5 ms
    p = 6 * (18_874_368 + 262_144) + 2048 * 18992
    assert dense == pytest.approx(6 * p * 16384 / 197e12)
    cell = toy_cell()
    facts = {"model_config": model_cfg, "sequence_length": 16384,
             "global_batch": 1, "chips": 1, "dtype_bytes": 2,
             "sa_entries": {"computed": 43.0, "needed": 10.0},
             "scope_seconds": {"all": 8 * 2.0, "attn.sa": 8 * 0.5,
                               "sa.indexer": 8 * 0.4},
             "sa_indexer_matmul_s": 8 * 0.02}
    ctx = {"facts": facts, "peaks": PEAKS,
           "trace": types.SimpleNamespace(steps=8, matmul_s=8 * 0.1)}
    read = cell.metric_reader
    assert read("sa_attention_roofline")(ctx) == pytest.approx(
        100 * att / 0.5)
    assert read("sa_indexer_roofline")(ctx) == pytest.approx(100 * idx / 0.4)
    assert read("sa_entries_computed_over_needed")(ctx) == 4.3
    assert read("sa_indexer_share_pct.train")(ctx) == pytest.approx(20.0)
    # the dots outside the indexer's: 0.1 - 0.02 s a step
    assert read("sa_dense_dot_roofline")(ctx) == pytest.approx(
        100 * dense / 0.08)
    # a program without the scopes or the counter (the parent): nothing to
    # read, no error
    for name in ("sa_attention_roofline", "sa_indexer_roofline",
                 "sa_entries_computed_over_needed",
                 "sa_indexer_share_pct.train", "sa_dense_dot_roofline"):
        assert read(name)({"facts": {}, "trace": None, "peaks": None}) is None
        assert read(name)({"facts": {
            "scope_seconds": {"all": 1.0, "attn.mla": 0.5},
            "sa_entries": {"computed": 0.0, "needed": 0.0}},
            "trace": types.SimpleNamespace(steps=8, matmul_s=0.0),
            "peaks": PEAKS}) is None


def test_every_reader_of_the_cell_reads_the_drivers_scopes(model_cfg):
    """A traced run's facts as this driver makes them (a scope table over
    its own ``SCOPES``): every per-layer metric the cell reports reads them
    without an error."""
    from harness import trace
    cell = spec.load_cell("keye_vl2_30b_a3b.fit.sa16k")
    by = {n: 0.01 for n in sa_lm_fit_cell.SCOPES}
    by.update({f"{n}:kernels": 0.005 for n in sa_lm_fit_cell.SCOPES})
    by.update({"moe.shared": 0.0, "all": 4.0})
    reduction = trace.TraceReduction(
        chips=1, window_s=4.2, busy_s=4.1, busy_s_least=4.1, steps=8,
        step_program="train", step_intervals_s=[0.5] * 7, matmul_s=1.0,
        collective_s=0.0, collective_exposed_s=0.0)
    facts = {"chips": 1, "global_batch": 1, "sequence_length": 16384,
             "train_flops_per_sample": 80e12, "dtype_bytes": 2,
             "model_config": model_cfg, "scope_seconds": by,
             "sa_entries": {"computed": 4.3, "needed": 1.0},
             "sa_indexer_matmul_s": 0.2,
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
    assert read["sa_entries_computed_over_needed"] == 4.3
    assert read["sa_indexer_share_pct.train"] == pytest.approx(0.25)
    assert read["sa_dense_dot_roofline"] is not None
    assert read["moe_share_pct.train"] == pytest.approx(100 * 0.02 / 4.0)


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


def test_sa_step_compiles_and_fits_the_chip(topo, monkeypatch):
    """The cell's train step at its own size (one 16384-token sequence,
    659.2 M parameters) compiled for one chip of a described v5e: it fits
    16 GB and fills more than a quarter of it; every block's attention
    reaches the selected-key flash kernels (forward and one fused
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
                                          "keye_vl2_30b_a3b_ep8.json"))
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
                   for a in jax.tree.leaves(params)) == 659_190_016

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
