"""CPU rehearsals of the grouped-query token cells' driver
(``harness/gqa_lm_fit_cell.py``): the real ``TPUEstimator.fit`` on arrays of
ids at a toy size with sliding and global layers, the result line, `correct`
coming out false for the control, for faults under the timed path and for the
two faults of this mechanism; ``work_gqa.py`` pinned to ISSUE 39's
arithmetic; the new readers on hand-made facts. Not part of tier-1."""

import json
import os
import time
import types

import pytest

import tampers
from harness import check, fit_cell, gqa_lm_fit_cell, runner, spec, work_gqa

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(BENCH, "tests", "data", "toy_gqa")
SEED = 2_147_483_659


def toy_cell():
    return spec.load_cell("toy_gqa.fit", os.path.join(TOY, "BENCHMARK.json"),
                          [BENCH, TOY])


def run_toy(tmp_path, traced=False, tamper=None):
    cell = toy_cell()
    out = gqa_lm_fit_cell.run(cell, SEED, 0.5, traced, time.perf_counter(),
                              cluster_mode="local",
                              scratch=str(tmp_path / "run"), tamper=tamper)
    line, code = runner.assemble(cell, out, traced)
    assert code == 0
    json.dumps(line)
    return line, out


def test_gqa_token_cell_end_to_end(tmp_path):
    line, out = run_toy(tmp_path)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s_per_chip"}
    w = out["facts"]["window"]
    assert w["epochs"] >= 1 and w["samples"] == w["epochs"] * 16
    assert w["compiles_in_window"] == 0
    assert line["compared"]["moe_dropped_rows"]["value"] == 0
    assert out["facts"]["moe"]["moe_local_rows"] > 0
    # the model's view: the layers from first_published_layer on
    assert out["facts"]["model_config"]["layer_types"] == [
        "sliding_attention", "full_attention", "sliding_attention"]
    tiles = out["facts"]["window_tiles"]
    assert tiles["visited"] == tiles["needed"] > 0
    assert not os.path.exists(tmp_path / "run")


def test_traced_run_reports_what_it_can_read(tmp_path):
    line, out = run_toy(tmp_path, traced=True)
    # no device plane on the CPU: the device trace's readers find nothing
    # and are left out; the counters' readers report
    assert {"moe_rows_max_over_mean", "compile_s", "infeed_stall_pct.train",
            "infeed_assemble_ms.train", "swa_tiles_visited_over_needed"} \
        <= set(line["metrics"])
    assert line["metrics"]["swa_tiles_visited_over_needed"]["value"] == 1.0
    assert not {"swa_attention_roofline", "global_attention_roofline",
                "gqa_expert_gmm_roofline", "gqa_dense_dot_roofline",
                "gqa_attention_share_pct.train", "mfu.train"} \
        & set(line["metrics"])
    assert out["facts"]["scope_seconds"] is None


def sliding_layers_run_causal(prep):
    """The program's sliding layers lose their window (their RoPE stays)."""
    eng = prep.est.engine
    eng.module = eng.module.clone(
        layer_windows=(None,) * len(eng.module.layer_windows))
    eng._jit_train = None


@pytest.mark.parametrize("tamper,caught_by", [
    (tampers.half_batch, "grad_diff_median"),
    (sliding_layers_run_causal, "grad_diff_median"),
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
    prep = gqa_lm_fit_cell.prepare(cell, SEED, mesh, devices)
    gqa_lm_fit_cell.first_steps(prep)
    fit_cell.free_program(prep)
    reference = cell.load("reference")
    ref = gqa_lm_fit_cell.reference_readings(prep)
    sides = {"control": gqa_lm_fit_cell.reference_readings(
        prep, quant=nn.fp8_quant)}
    for fault in gqa_lm_fit_cell.FAULTS:
        sides[fault] = gqa_lm_fit_cell.reference_readings(prep, fault=fault)
    for name, side in sides.items():
        numbers = gqa_lm_fit_cell.compare_sides(side, ref, prep.shapes,
                                                reference)
        limits = {k: v for k, v in cell.limits.items() if k in numbers}
        correct, table = check.verdict(numbers, limits)
        assert correct is False, (name, table)
    with pytest.raises(ValueError):
        gqa_lm_fit_cell.reference_readings(prep, fault="no_such_fault")


@pytest.fixture(scope="module")
def model_cfg():
    cfg = spec.load_json(os.path.join(BENCH, "configs",
                                      "trinity_mini_ep8.json"))
    return spec.load_py(os.path.join(BENCH, cfg["factory"])).model_config(cfg)


def test_work_gqa_is_issue_39s_arithmetic(model_cfg):
    assert model_cfg["layer_types"] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert work_gqa.blocks(model_cfg) == {
        "dense": 1, "expert": 4, "all": 5, "sliding_attention": 4,
        "full_attention": 1}
    m = work_gqa.matrices(model_cfg)
    assert m["attention"] == 27_262_976                 # 27.26 M
    assert m["attention"] + m["dense_ffn"] == 65_011_712        # 65.0 M
    assert m["expert"] == m["shared"] == 6_291_456 and m["router"] == 262_144
    assert 2 * m["head"] == 102_498_304                 # 102.5 M
    assert work_gqa.param_count(model_cfg) == 705_473_792       # 705.5 M
    assert work_gqa.score_entries(16384, 2048) == 31_458_304    # 31.5 M
    assert work_gqa.score_entries(16384, None) == 134_225_920   # 134 M
    assert work_gqa.score_entries(64, 2048) == \
        work_gqa.score_entries(64, None)
    swa = work_gqa.attention_flops_per_sequence(model_cfg, 16384,
                                                "sliding_attention")
    full = work_gqa.attention_flops_per_sequence(model_cfg, 16384,
                                                 "full_attention")
    assert full["fwd"] == pytest.approx(2.2e12, rel=0.005)
    assert 4 * swa["fwd"] == pytest.approx(2.06e12, rel=0.005)
    # a step: 1 sequence, 16384 rows a layer to the held experts
    step = work_gqa.train_flops_per_sample(model_cfg, 16384, 4 * 16384)
    attention = 3 * (full["fwd"] + 4 * swa["fwd"])
    assert attention == pytest.approx(12.8e12, rel=0.005)
    assert 6 * work_gqa.dense_params_per_token(model_cfg) * 16384 == \
        pytest.approx(24.7e12, rel=0.005)
    assert step - attention == pytest.approx(27.2e12, rel=0.005)
    assert step == pytest.approx(40.0e12, rel=0.005)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_work_gqa_least_times_and_the_readers(model_cfg):
    full = work_gqa.attention_min_seconds(model_cfg, 16384, 1, 2, PEAKS,
                                          "full_attention")
    assert full == pytest.approx(3 * 2.2e12 / 197e12, rel=0.005)  # compute
    swa = work_gqa.attention_min_seconds(model_cfg, 16384, 1, 2, PEAKS,
                                         "sliding_attention")
    assert swa == pytest.approx(3 * 2.06e12 / 197e12, rel=0.005)
    # at 64 positions the bytes decide: q and the output at 32 heads, k and
    # v at 4, forward once and backward twice
    io = 64 * 2 * (32 + 4) * 128 * 2
    assert work_gqa.attention_min_seconds(
        model_cfg, 64, 1, 2, PEAKS, "full_attention") == \
        pytest.approx(3 * io / 819e9)
    weights = 4 * 16 * 6_291_456 * 2
    assert work_gqa.expert_min_seconds(model_cfg, 4 * 64, 2, PEAKS) == \
        pytest.approx(3 * weights / 819e9, rel=0.01)            # memory
    dense = work_gqa.dense_params_per_token(model_cfg)
    cell = toy_cell()
    facts = {"model_config": model_cfg, "sequence_length": 16384,
             "global_batch": 1, "chips": 1, "dtype_bytes": 2,
             "moe": {"moe_local_rows": 4 * 16384},
             "window_tiles": {"visited": 30.0, "needed": 20.0},
             "scope_seconds": {"all": 8 * 0.7, "attn.gqa": 8 * 0.35,
                               "attn.window:kernels": 8 * 0.05,
                               "attn.global:kernels": 8 * 0.06,
                               "moe.experts": 8 * 0.1}}
    ctx = {"facts": facts, "peaks": PEAKS,
           "trace": types.SimpleNamespace(steps=8, matmul_s=8 * 0.2)}
    read = cell.metric_reader
    assert read("swa_attention_roofline")(ctx) == pytest.approx(
        100 * swa / 0.05)
    assert read("global_attention_roofline")(ctx) == pytest.approx(
        100 * full / 0.06)
    assert read("gqa_attention_share_pct.train")(ctx) == pytest.approx(50.0)
    assert read("gqa_dense_dot_roofline")(ctx) == pytest.approx(
        100 * 6 * dense * 16384 / 197e12 / 0.2)
    assert read("gqa_expert_gmm_roofline")(ctx) == pytest.approx(
        100 * work_gqa.expert_min_seconds(model_cfg, 4 * 16384, 2, PEAKS)
        / 0.1)
    assert read("swa_tiles_visited_over_needed")(ctx) == 1.5
    # a program without the scopes or the counter (the parent): nothing to
    # read, no error
    for name in ("swa_attention_roofline", "global_attention_roofline",
                 "gqa_attention_share_pct.train", "gqa_expert_gmm_roofline",
                 "gqa_dense_dot_roofline", "swa_tiles_visited_over_needed"):
        assert read(name)({"facts": {}, "trace": None, "peaks": None}) is None
        assert read(name)({"facts": {
            "scope_seconds": {"all": 1.0, "attn.mla": 0.5},
            "window_tiles": {"visited": 0.0, "needed": 0.0}},
            "trace": types.SimpleNamespace(steps=8, matmul_s=0.0),
            "peaks": PEAKS}) is None
