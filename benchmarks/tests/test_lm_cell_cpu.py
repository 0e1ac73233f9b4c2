"""CPU rehearsals of the token cells' driver (``harness/lm_fit_cell.py``):
the real ``TPUEstimator.fit`` on arrays of ids at a toy size, the result
line, `correct` coming out false for the control and for faults under the
timed path; the token generator; ``work_lm.py`` pinned to ISSUE 35's
arithmetic; the scope reader on a hand-made trace. Not part of tier-1."""

import functools
import json
import os
import time
import types

import numpy as np
import pytest

import tampers
from harness import (check, fit_cell, lm_fit_cell, runner, scopes, spec,
                     tokens, work_lm, xplane)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(BENCH, "tests", "data", "toy_lm")
SEED = 2_147_483_659


def toy_cell():
    return spec.load_cell("toy_lm.fit", os.path.join(TOY, "BENCHMARK.json"),
                          [BENCH, TOY])


def run_toy(tmp_path, traced=False, tamper=None):
    cell = toy_cell()
    out = lm_fit_cell.run(cell, SEED, 0.5, traced, time.perf_counter(),
                          cluster_mode="local",
                          scratch=str(tmp_path / "run"), tamper=tamper)
    line, code = runner.assemble(cell, out, traced)
    assert code == 0
    json.dumps(line)
    return line, out


def test_token_cell_end_to_end(tmp_path):
    line, out = run_toy(tmp_path)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s_per_chip"}
    w = out["facts"]["window"]
    assert w["epochs"] >= 1 and w["samples"] == w["epochs"] * 16
    assert w["compiles_in_window"] == 0
    assert line["compared"]["moe_dropped_rows"]["value"] == 0
    assert out["facts"]["moe"]["moe_local_rows"] > 0
    assert out["facts"]["tokens_per_s_per_chip"] == pytest.approx(
        64 * line["metrics"]["train_samples_per_s_per_chip"]["value"])
    assert not os.path.exists(tmp_path / "run")


def test_traced_run_reports_what_it_can_read(tmp_path):
    line, out = run_toy(tmp_path, traced=True)
    # no device plane on the CPU: the device trace's readers and the scope
    # readers find nothing and are left out; the counters' readers report
    assert {"moe_rows_max_over_mean", "compile_s", "infeed_stall_pct.train",
            "infeed_assemble_ms.train"} <= set(line["metrics"])
    assert not {"mla_attention_roofline", "expert_gmm_roofline",
                "dense_dot_roofline", "attention_share_pct.train",
                "mfu.train"} & set(line["metrics"])
    assert out["facts"]["scope_seconds"] is None
    assert out["facts"]["traced"]["steps"] == 4


def no_mtp_loss(prep):
    """The step trains on the main head's loss alone."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import \
        next_token_loss
    eng = prep.est.engine
    eng.loss_fn = functools.partial(next_token_loss, mtp_weight=0.0)
    eng._jit_train = None


@pytest.mark.parametrize("tamper,caught_by", [
    (tampers.half_batch, "grad_diff_median"),
    (no_mtp_loss, "loss_gap_1"),
    (tampers.state_unchanged, "dparam_diff_median"),
])
def test_a_fault_under_the_timed_path_is_not_correct(tmp_path, tamper,
                                                     caught_by):
    line, _ = run_toy(tmp_path, tamper=tamper)
    assert line["correct"] is False
    row = line["compared"][caught_by]
    assert row["value"] > row["limit"]


def test_the_control_is_not_correct():
    from reference import nn
    cell = toy_cell()
    mesh, devices = fit_cell.open_context(cell, "local")
    prep = lm_fit_cell.prepare(cell, SEED, mesh, devices)
    lm_fit_cell.first_steps(prep)
    fit_cell.free_program(prep)
    reference = cell.load("reference")
    ref = lm_fit_cell.reference_readings(prep)
    control = lm_fit_cell.reference_readings(prep, quant=nn.fp8_quant)
    numbers = lm_fit_cell.compare_sides(control, ref, prep.shapes, reference)
    limits = {k: v for k, v in cell.limits.items() if k in numbers}
    correct, table = check.verdict(numbers, limits)
    assert correct is False, table
    assert numbers["choice_diff_share"] > 0


def test_token_generator():
    traffic = spec.load_json(os.path.join(BENCH, "traffic",
                                          "tokens_packed_8k.json"))
    a = tokens.make_sequences(traffic, 16160, SEED)
    assert a.shape == (16, 8192) and a.dtype == np.uint16
    assert np.array_equal(a, tokens.make_sequences(traffic, 16160, SEED))
    assert not np.array_equal(a, tokens.make_sequences(traffic, 16160, 1))
    assert a.max() < 16160
    flat = a.reshape(-1)
    ends = np.flatnonzero(flat == 0)          # EOS ends a document only
    docs = np.diff(np.concatenate([[-1], ends]))
    assert docs.min() >= 16 and docs.max() <= 8192
    assert 300 < np.median(docs) < 900
    counts = np.bincount(flat, minlength=16160)[1:]
    assert counts[0] > 5 * counts[9] > 0      # Zipf: rank 1 ~ 10 x rank 10
    fed = [[(a[:2], a[:2]), (a[2:4], a[2:4])]]
    assert tokens.count_bad_rows(a, fed) == {"rows": 4, "bad": 0}
    wrong = a[:2].copy()
    wrong[0, 5] ^= 1
    assert tokens.count_bad_rows(a, [[(wrong, wrong)]])["bad"] == 1
    assert tokens.count_bad_rows(a, [[(a[:2], a[:2]),
                                      (a[1:3], a[1:3])]])["bad"] == 1


@pytest.fixture(scope="module")
def model_cfg():
    cfg = spec.load_json(os.path.join(BENCH, "configs",
                                      "joyai_llm_flash_ep16.json"))
    return spec.load_py(os.path.join(BENCH, cfg["factory"])).model_config(cfg)


def test_work_lm_is_issue_35s_arithmetic(model_cfg):
    m = work_lm.matrices(model_cfg)
    assert m["mla"] == 26_345_472                       # 26.35 M
    assert m["dense_ffn"] == 3 * 2048 * 7168
    assert m["expert"] == 4_718_592 and m["shared"] == 4_718_592
    assert work_lm.param_count(model_cfg) == 680_439_808
    att = work_lm.attention_flops_per_sequence(model_cfg, 8192)
    assert att["fwd"] == 8192 ** 2 * 32 * 320
    # a step: 2 sequences, 8192 rows a layer to the held experts
    step = 2 * work_lm.train_flops_per_sample(model_cfg, 8192, 5 * 8192 / 2)
    attention = 2 * 6 * (att["fwd"] + att["bwd"])
    assert attention == pytest.approx(24.7e12, rel=0.005)
    assert step - attention == pytest.approx(30.9e12, rel=0.005)
    assert step == pytest.approx(55.7e12, rel=0.005)
    # more rows routed here, more work counted
    assert work_lm.train_flops_per_sample(model_cfg, 8192, 40960) > step / 2


def test_work_lm_least_times(model_cfg):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = work_lm.attention_min_seconds(model_cfg, 8192, 2, 2, peaks)
    assert least == pytest.approx(24.7e12 / 197e12, rel=0.005)  # compute
    few = work_lm.expert_min_seconds(model_cfg, 5 * 64, 2, peaks)
    many = work_lm.expert_min_seconds(model_cfg, 5 * 65536, 2, peaks)
    weights = 5 * 16 * 4_718_592 * 2
    assert few == pytest.approx(3 * weights / 819e9, rel=0.01)   # memory
    assert many == pytest.approx(
        3 * 2 * 4_718_592 * 5 * 65536 / 197e12, rel=0.01)        # compute
    dense = work_lm.dense_params_per_token(model_cfg)
    assert dense == pytest.approx(302.9e6, rel=0.005)
    assert work_lm.dense_min_seconds(model_cfg, 16384, 2, peaks) == \
        pytest.approx(6 * dense * 16384 / 197e12)                # compute
    assert work_lm.dense_min_seconds(model_cfg, 16, 2, peaks) == \
        pytest.approx(3 * dense * 2 / 819e9)                     # memory
    # the reader: that floor over the trace's dot time a step
    ctx = {"facts": {"model_config": model_cfg, "sequence_length": 8192,
                     "global_batch": 2, "chips": 1, "dtype_bytes": 2},
           "trace": types.SimpleNamespace(steps=8, matmul_s=8 * 0.2),
           "peaks": peaks}
    assert toy_cell().metric_reader("dense_dot_roofline")(ctx) == \
        pytest.approx(100 * 6 * dense * 16384 / 197e12 / 0.2)


def hand_made_trace(path, with_scopes=True):
    """Device 0: two steps, each a fusion (100 ns), a Pallas custom call
    (300) and a ``while`` (150) around one operation of its body (50); their
    metadata carry the path in a string stat (one of them by reference)."""
    space = xplane._build()() if xplane._XSpace is None else xplane._XSpace()
    host = space.planes.add(name="/host:CPU", id=1)
    e = host.event_metadata.add(key=1)
    e.value.id, e.value.name = 1, "bench:traced_steps"
    line = host.lines.add(name="python", timestamp_ns=0)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=int(3e6))
    dev = space.planes.add(name="/device:TPU:0", id=2)
    for sid, name in ((1, "hlo_category"), (2, "tf_op"),
                      (3, "jit(_train_step)/optimizer/while/body/mul")):
        s = dev.stat_metadata.add(key=sid)
        s.value.id, s.value.name = sid, name
    ops = [(1, "%fusion.1 = bf16[8] fusion(bf16[8] %p)",
            "jit(_train_step)/jvp(forward)/layers_1/attn.mla/dot_general"),
           (2, "%custom-call.2 = bf16[8] custom-call(bf16[8] %p), "
               "custom_call_target=\"tpu_custom_call\"",
            "jit(_train_step)/transpose(jvp(forward))/mtp/attn.mla/pallas"),
           (3, "%fusion.3 = f32[8] fusion(f32[8] %p)", None),
           (4, "%while.4 = (f32[8]) while((f32[8]) %t), body=%b",
            "jit(_train_step)/while")]
    for mid, text, path_ in ops:
        m = dev.event_metadata.add(key=mid)
        m.value.id, m.value.name = mid, text
        if not with_scopes:
            continue
        st = m.value.stats.add(metadata_id=2)
        if path_ is None:
            st.ref_value = 3
        else:
            st.str_value = path_
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for step in range(2):
        t = step * 1e6
        for k, at, dur in ((1, 1e5, 100), (2, 2e5, 300), (4, 5e5, 150),
                           (3, 5.5e5, 50)):
            line.events.add(metadata_id=k, offset_ps=int(t + at),
                            duration_ps=int(dur * 1e3))
    # an operation after the traced span is left out
    line.events.add(metadata_id=1, offset_ps=int(4e6), duration_ps=int(1e6))
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_scope_seconds_on_a_hand_made_trace(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    hand_made_trace(path)
    by = scopes.scope_seconds(path, ("attn.mla", "mtp", "optimizer",
                                     "moe.experts"))
    # the while counts for the 100 ns in which its body's operation does
    # not run: 100 + 300 + 100 + 50 a step
    assert by["all"] == pytest.approx(1100e-9)
    assert by["attn.mla"] == pytest.approx(800e-9)
    assert by["attn.mla:kernels"] == pytest.approx(600e-9)
    assert by["mtp"] == pytest.approx(600e-9)
    assert by["optimizer"] == pytest.approx(100e-9)       # by reference
    assert by["moe.experts"] == 0.0
    ctx = {"facts": {"scope_seconds": dict(by, **{"moe.router": 0.0,
                                                  "moe.shared": 0.0})}}
    cell = toy_cell()
    assert cell.metric_reader("attention_share_pct.train")(ctx) == \
        pytest.approx(100 * 800 / 1100)
    assert cell.metric_reader("optimizer_share_pct.train")(ctx) == \
        pytest.approx(100 * 100 / 1100)
    assert cell.metric_reader("moe_share_pct.train")(ctx) == 0.0
    # a program without the scopes (the parent): nothing to read, no error
    hand_made_trace(path, with_scopes=False)
    assert scopes.scope_seconds(path, ("attn.mla",)) is None
    for name in ("attention_share_pct.train", "mla_attention_roofline",
                 "expert_gmm_roofline", "dense_dot_roofline",
                 "moe_rows_max_over_mean"):
        assert cell.metric_reader(name)(
            {"facts": {}, "trace": None, "peaks": None}) is None
