"""CPU rehearsals of the ``nemotron_h`` token cells' driver
(``harness/hybrid_lm_fit_cell.py``): the real ``TPUEstimator.fit`` on arrays
of ids at a toy size with all three mixers and the MTP module, the result
line, `correct` coming out false for the control, for faults under the timed
path and for the three faults of this mechanism; ``work_hybrid.py`` pinned to
ISSUE 41's arithmetic; the new readers on hand-made facts. Not part of
tier-1."""

import json
import os
import time
import types

import pytest

import tampers
from harness import (check, fit_cell, hybrid_lm_fit_cell, runner, spec,
                     work_hybrid)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(BENCH, "tests", "data", "toy_hybrid")
SEED = 2_147_483_659


def toy_cell():
    return spec.load_cell("toy_hybrid.fit",
                          os.path.join(TOY, "BENCHMARK.json"), [BENCH, TOY])


def run_toy(tmp_path, traced=False, tamper=None):
    cell = toy_cell()
    out = hybrid_lm_fit_cell.run(cell, SEED, 0.5, traced, time.perf_counter(),
                                 cluster_mode="local",
                                 scratch=str(tmp_path / "run"), tamper=tamper)
    line, code = runner.assemble(cell, out, traced)
    assert code == 0
    json.dumps(line)
    return line, out


def test_hybrid_token_cell_end_to_end(tmp_path):
    line, out = run_toy(tmp_path)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s_per_chip"}
    w = out["facts"]["window"]
    assert w["epochs"] >= 1 and w["samples"] == w["epochs"] * 16
    assert w["compiles_in_window"] == 0
    for held_at_zero in ("moe_dropped_rows", "attention_reference_on_tpu",
                         "ssm_sequential_scan_on_tpu", "infeed_bad_rows"):
        assert line["compared"][held_at_zero] == {"value": 0, "limit": 0}
    assert out["facts"]["moe"]["moe_local_rows"] > 0
    # the model's view: the layers from first_published_layer on, the
    # published head counts with the share beside them
    mcfg = out["facts"]["model_config"]
    assert mcfg["hybrid_override_pattern"] == "MEM*E"
    assert (mcfg["mamba_num_heads"], mcfg["mixer_parallel_size"]) == (8, 2)
    assert (mcfg["n_routed_experts"], mcfg["experts_held"],
            mcfg["first_expert"]) == (32, 4, 4)
    assert not os.path.exists(tmp_path / "run")


def test_traced_run_reports_what_it_can_read(tmp_path):
    line, out = run_toy(tmp_path, traced=True)
    # no device plane on the CPU: the device trace's readers find nothing
    # and are left out; the counters' readers report
    assert {"moe_rows_max_over_mean", "moe_rows_moved_over_routed",
            "compile_s", "infeed_stall_pct.train",
            "infeed_assemble_ms.train"} <= set(line["metrics"])
    assert not {"ssm_share_pct.train", "ssd_scan_roofline",
                "latent_expert_gmm_roofline", "hybrid_dense_dot_roofline",
                "moe_router_share_pct.train", "mfu.train"} \
        & set(line["metrics"])
    assert out["facts"]["scope_seconds"] is None
    assert out["facts"]["scan_matmul_s"] is None


def scan_loses_its_carry(prep):
    """The program's scan runs every chunk from a zero state."""
    from analytics_zoo_tpu.ops import ssm
    real = ssm._ssd_chunked

    def cut(x, dt, a, b, c, d, chunk):
        fold = (lambda t: t.reshape((-1, chunk) + t.shape[2:]))
        return real(fold(x), fold(dt), a, fold(b), fold(c), d,
                    chunk).reshape(x.shape)

    ssm._ssd_chunked = cut
    prep.est.engine._jit_train = None


@pytest.mark.parametrize("tamper,caught_by", [
    (tampers.half_batch, "grad_diff_median"),
    (scan_loses_its_carry, "grad_diff_median"),
    (tampers.state_unchanged, "dparam_diff_median"),
])
def test_a_fault_under_the_timed_path_is_not_correct(tmp_path, monkeypatch,
                                                     tamper, caught_by):
    from analytics_zoo_tpu.ops import ssm
    monkeypatch.setattr(ssm, "_ssd_chunked", ssm._ssd_chunked)  # put back
    line, _ = run_toy(tmp_path, tamper=tamper)
    assert line["correct"] is False
    row = line["compared"][caught_by]
    assert row["value"] > row["limit"]


def test_the_control_and_the_mechanisms_faults_are_not_correct():
    from reference import nn
    cell = toy_cell()
    mesh, devices = fit_cell.open_context(cell, "local")
    prep = hybrid_lm_fit_cell.prepare(cell, SEED, mesh, devices)
    hybrid_lm_fit_cell.first_steps(prep)
    fit_cell.free_program(prep)
    reference = cell.load("reference")
    ref = hybrid_lm_fit_cell.reference_readings(prep)
    sides = {"control": hybrid_lm_fit_cell.reference_readings(
        prep, quant=nn.fp8_quant)}
    for fault in hybrid_lm_fit_cell.FAULTS:
        sides[fault] = hybrid_lm_fit_cell.reference_readings(prep,
                                                             fault=fault)
    for name, side in sides.items():
        numbers = hybrid_lm_fit_cell.compare_sides(side, ref, prep.shapes,
                                                   reference)
        limits = {k: v for k, v in cell.limits.items() if k in numbers}
        correct, table = check.verdict(numbers, limits)
        print(name, {k: round(v["value"], 5) for k, v in table.items()})
        assert correct is False, (name, table)
    with pytest.raises(ValueError):
        hybrid_lm_fit_cell.reference_readings(prep, fault="no_such_fault")


@pytest.fixture(scope="module")
def model_cfg():
    cfg = spec.load_json(os.path.join(BENCH, "configs",
                                      "nemotron3_super_tp8_ep64.json"))
    return spec.load_py(os.path.join(BENCH, cfg["factory"])).model_config(cfg)


def test_work_hybrid_is_issue_41s_arithmetic(model_cfg):
    assert model_cfg["hybrid_override_pattern"] == "MEMEMEMEM*E"
    assert work_hybrid.blocks(model_cfg) == {"all": 11, "M": 5, "*": 1,
                                             "E": 5}
    z = work_hybrid.sizes(model_cfg)
    assert (z["m_heads"], z["groups"], z["heads"], z["kv"], z["held"]) == \
        (16, 1, 4, 1, 8)
    m = work_hybrid.matrices(model_cfg)
    assert m["mamba"] == 4096 * 2320 + 1024 * 4096          # 13.70 M
    assert m["attention"] == 2 * 2_097_152 + 2 * 524_288    # 5.25 M
    assert (m["router"], m["latent"], m["shared"], m["expert"]) == \
        (2_097_152, 2 * 4_194_304, 44_040_192, 5_505_024)
    assert 2 * m["head"] == 134_217_728                      # 134.2 M
    # conv 5 x 1280, A_log, dt_bias, D, the gated norm; a norm a block
    assert work_hybrid.small_params(model_cfg) == \
        5 * (5 * 1280 + 3 * 16 + 1024) + 12 * 4096
    assert work_hybrid.param_count(model_cfg) == 700_862_960  # 700.9 M
    dense = work_hybrid.dense_params_per_token(model_cfg)
    assert dense == 413_466_624
    # the causal triangle of 8192 at 4 heads of 128
    att = work_hybrid.attention_flops_per_sequence(model_cfg, 8192)
    assert att["fwd"] == 8192 * 8193 // 2 * 4 * 128 * 4
    # a chunk of 128: 8256 (i, j) pairs at 2 (128 + 16 x 64) each, and 4 P N
    # a head and position for the chunk's state, written and read
    scan = work_hybrid.scan_flops_per_sequence(model_cfg, 8192)
    assert scan["fwd"] == 64 * 8256 * 2 * (128 + 1024) \
        + 8192 * 4 * 16 * 64 * 128
    # a step: 1 sequence, 2816 rows a layer to the held experts at balance
    step = work_hybrid.train_flops_per_sample(model_cfg, 8192, 5 * 2816)
    assert step == pytest.approx(21.1e12, rel=0.003)
    assert step / 3 / 8192 == pytest.approx(0.859e9, rel=0.003)
    assert 6 * m["shared"] * 5 * 8192 / step == pytest.approx(0.51, abs=0.01)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_work_hybrid_least_times_and_the_readers(model_cfg):
    # the scan is bound by its bytes: x', z and y of 1024, B and C of 128,
    # dt of 16 a position, forward once and backward twice, five layers
    io = 8192 * (3 * 1024 + 2 * 128 + 16) * 2
    scan = work_hybrid.scan_min_seconds(model_cfg, 8192, 1, 2, PEAKS)
    assert scan == pytest.approx(5 * 3 * io / 819e9)
    assert scan / 15 == pytest.approx(0.067e-3, rel=0.01)   # ~0.1 ms a pass
    dense = work_hybrid.dense_min_seconds(model_cfg, 8192, 2, PEAKS)
    assert dense == pytest.approx(6 * 413_466_624 * 8192 / 197e12)
    weights = 5 * 8 * 5_505_024 * 2
    assert work_hybrid.expert_min_seconds(model_cfg, 5 * 64, 2, PEAKS) == \
        pytest.approx(3 * weights / 819e9, rel=0.02)            # memory
    experts = work_hybrid.expert_min_seconds(model_cfg, 5 * 2816, 2, PEAKS)
    cell = toy_cell()
    facts = {"model_config": model_cfg, "sequence_length": 8192,
             "global_batch": 1, "chips": 1, "dtype_bytes": 2,
             "moe": {"moe_local_rows": 5 * 2816},
             "scan_matmul_s": 12 * 0.02,
             "scope_seconds": {"all": 12 * 0.35, "ssm.mixer": 12 * 0.07,
                               "ssm.scan": 12 * 0.03, "attn.gqa": 12 * 0.035,
                               "moe.router": 12 * 0.007,
                               "moe.experts": 12 * 0.04}}
    ctx = {"facts": facts, "peaks": PEAKS,
           "trace": types.SimpleNamespace(steps=12, matmul_s=12 * 0.2)}
    read = cell.metric_reader
    assert read("ssm_share_pct.train")(ctx) == pytest.approx(20.0)
    assert read("moe_router_share_pct.train")(ctx) == pytest.approx(2.0)
    assert read("gqa_attention_share_pct.train")(ctx) == pytest.approx(10.0)
    assert read("ssd_scan_roofline")(ctx) == pytest.approx(100 * scan / 0.03)
    assert read("latent_expert_gmm_roofline")(ctx) == pytest.approx(
        100 * experts / 0.04)
    # the scan's own dots are taken out of the trace's conv-or-dot time
    assert read("hybrid_dense_dot_roofline")(ctx) == pytest.approx(
        100 * dense / 0.18)
    # a program without the scopes (the parent): nothing to read, no error
    for name in ("ssm_share_pct.train", "moe_router_share_pct.train",
                 "ssd_scan_roofline", "latent_expert_gmm_roofline",
                 "hybrid_dense_dot_roofline"):
        assert read(name)({"facts": {}, "trace": None, "peaks": None}) is None
        assert read(name)({"facts": {
            "scope_seconds": {"all": 1.0, "attn.mla": 0.5}},
            "trace": types.SimpleNamespace(steps=8, matmul_s=0.0),
            "peaks": PEAKS}) is None
