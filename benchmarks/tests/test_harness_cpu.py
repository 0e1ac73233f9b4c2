"""CPU rehearsals of the harness, below run.py's look for a chip: the real
``TPUEstimator.fit`` on the real ``ImageNetPipeline`` at toy sizes, one chip
and four virtual ones; the result line's schema; `correct` coming out false
for every fault planted under the timed path and for the control; and that a
cell is added with files and entries alone.

Slow for unit tests (a toy run compiles the program's step and the
reference's): a few minutes for the file. Not part of tier-1.
"""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

import tampers
from harness import check, fit_cell, runner, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(BENCH, "tests", "data", "toy")
TOY_JSON = os.path.join(TOY, "BENCHMARK.json")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def toy_cell(name, extra_dir=None, benchmark_json=TOY_JSON):
    dirs = [BENCH, TOY] + ([extra_dir] if extra_dir else [])
    return spec.load_cell(name, benchmark_json, dirs)


def run_toy(cell, tmp_path, traced=False, tamper=None, seed=2_147_483_659):
    import time
    out = fit_cell.run(cell, seed, 0.5, traced, time.perf_counter(),
                       cluster_mode="local", scratch=str(tmp_path / "run"),
                       tamper=tamper)
    line, code = runner.assemble(cell, out, traced)
    assert code == 0
    return line, out


def assert_schema(line, cell, traced):
    assert list(line)[:5] == RESULT_KEYS
    assert list(line)[-1] == "compared"
    assert isinstance(line["correct"], bool)
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert set(dev) >= {"platform", "kind", "count", "memory_peak_bytes"}
    names = {m["name"] for m in
             (cell.per_layer if traced else cell.end_to_end)}
    assert set(line["metrics"]) <= names
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}
    json.dumps(line)


def test_one_chip_run_end_to_end(tmp_path):
    cell = toy_cell("toy_resnet.fit")
    line, out = run_toy(cell, tmp_path)
    assert_schema(line, cell, traced=False)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s_per_chip"}
    w = out["facts"]["window"]
    assert w["epochs"] >= 1 and w["samples"] == w["epochs"] * 256
    assert w["seconds"] >= 0.5 and w["compiles_in_window"] == 0
    assert line["metrics"]["train_samples_per_s_per_chip"]["value"] == \
        pytest.approx(w["samples"] / w["seconds"])
    assert not os.path.exists(tmp_path / "run")      # nothing left behind


def test_traced_run_reports_per_layer_metrics_it_can_read(tmp_path):
    cell = toy_cell("toy_resnet.fit")
    line, out = run_toy(cell, tmp_path, traced=True)
    assert_schema(line, cell, traced=True)
    # the CPU has no device plane: the readers of the device trace return
    # nothing and are left out; the counters' readers report
    assert {"infeed_stall_pct.train", "infeed_assemble_ms.train",
            "compile_s"} <= set(line["metrics"])
    assert "device_idle_pct.train" not in line["metrics"]
    assert "collective_exposed_pct.train" not in line["metrics"]
    assert out["facts"]["traced"]["samples"] == 8 * 32   # one toy epoch


def test_four_chip_path_on_four_virtual_devices(tmp_path):
    cell = toy_cell("toy_resnet.fit.dp4")
    line, out = run_toy(cell, tmp_path)
    assert_schema(line, cell, traced=False)
    assert line["correct"], line["compared"]
    assert out["facts"]["global_batch"] == 128
    assert len(out["devices"]) == 4


def test_inception_factory_and_reference_agree(tmp_path):
    cell = toy_cell("toy_inception.fit")
    line, _ = run_toy(cell, tmp_path)
    assert line["correct"], line["compared"]


@pytest.mark.parametrize("cell_name,tamper,caught_by", [
    ("toy_resnet.fit", tampers.state_unchanged, "dparam_gap_median"),
    ("toy_resnet.fit", tampers.half_batch, "grad_gap_median"),
    ("toy_resnet.fit", tampers.altered_row, "infeed_bad_rows"),
    ("toy_resnet.fit.dp4", tampers.no_exchange, "grad_gap_median"),
])
def test_a_fault_under_the_timed_path_is_not_correct(tmp_path, cell_name,
                                                     tamper, caught_by):
    cell = toy_cell(cell_name)
    line, _ = run_toy(cell, tmp_path, tamper=tamper)
    assert line["correct"] is False
    row = line["compared"][caught_by]
    assert row["value"] > row["limit"]


def test_the_control_is_not_correct(tmp_path):
    """The reference through 8-bit float products, in the program's place."""
    from reference import nn
    cell = toy_cell("toy_resnet.fit")
    mesh, devices = fit_cell.open_context(cell, "local")
    prep = fit_cell.prepare(cell, 2_147_483_659, mesh, devices,
                            str(tmp_path / "data"))
    fit_cell.first_steps(prep)
    fit_cell.free_program(prep)
    ref = fit_cell.reference_readings(prep)
    control = fit_cell.reference_readings(prep, quant=nn.fp8_quant)
    numbers = fit_cell.compare_sides(control, ref, prep.shapes)
    limits = {k: v for k, v in cell.limits.items() if k in numbers}
    correct, table = check.verdict(numbers, limits)
    assert correct is False, table


def test_run_py_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet50.fit.imagenet", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_adding_a_cell_is_files_and_entries(tmp_path):
    """A throw-away configuration, traffic mix, per-layer metric and cell,
    registered from a directory of their own with appended entries; no file
    of the benchmark is edited and the harness runs the new cell."""
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic", "layer_metrics", "cells"):
        (extra / sub).mkdir(parents=True)
    with open(os.path.join(TOY, "configs", "toy_resnet.json")) as f:
        cfg = json.load(f)
    cfg.update(name="throwaway", stage_sizes=[1, 1, 1, 2], per_chip_batch=16)
    (extra / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (extra / "traffic" / "tiny.json").write_text(json.dumps({
        "name": "tiny", "kind": "image_shards", "image_size": 36, "crop": 32,
        "num_classes": 10, "shard_images": 64, "images": 128,
        "shuffle": True}))
    (extra / "layer_metrics" / "epochs_traced.py").write_text(
        "def read(ctx):\n"
        "    return float(ctx['facts']['traced']['steps'])\n")
    shutil.copy(os.path.join(TOY, "cells", "toy_resnet.fit.json"),
                extra / "cells" / "throwaway.fit.json")
    with open(TOY_JSON) as f:
        bench = json.load(f)
    before = copy.deepcopy(bench)
    bench["configs"].append({"name": "throwaway", "source": "test",
                             "file": "x", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway.fit", "config": "throwaway",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "epochs_traced", "unit": "epochs", "better": "higher",
        "source": "program_counter", "layer": "estimator",
        "moves": "train_samples_per_s_per_chip",
        "workloads": ["throwaway.fit"]})
    for key in before:      # entries appended, none changed
        if isinstance(before[key], list):
            assert bench[key][:len(before[key])] == before[key]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = toy_cell("throwaway.fit", str(extra), str(path))
    line, out = run_toy(cell, tmp_path, traced=True)
    assert line["metrics"]["epochs_traced"] == {
        "value": 8.0, "unit": "epochs"}
    assert out["facts"]["steps_per_epoch"] == 8
    # the cells that were there do not report the new metric
    assert "epochs_traced" not in {
        m["name"] for m in toy_cell("toy_resnet.fit", str(extra),
                                    str(path)).per_layer}
