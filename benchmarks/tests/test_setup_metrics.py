"""The six readers that split ``setup_s`` (PR 43): each exact on counters set
by hand in a registry of the test's own, nothing to read where the registry
has no such family, ``step_load_s`` 0 beside a run that compiled; and one
traced toy run whose result line carries all six, registered with appended
entries in a copied ``BENCHMARK.json``, no file of the benchmark edited."""

import json
import math
import os

import pytest

from analytics_zoo_tpu.obs.registry import MetricsRegistry
from harness import spec
from test_harness_cpu import BENCH, TOY, TOY_JSON, run_toy, toy_cell

NAMES = ("engine_build_s", "engine_build_jax_compile_s", "step_lower_s",
         "step_load_s", "step_first_call_s", "setup_program_s")


def read(name, registry):
    return spec.load_py(os.path.join(
        BENCH, "layer_metrics", f"{name}.py")).read({"registry": registry})


def registry_with(stages=None, jax_events=None):
    reg = MetricsRegistry()
    if stages is not None:
        fam = reg.counter("zoo_setup_seconds_total", "", ("stage",))
        for stage, s in stages.items():
            fam.labels(stage=stage).inc(s)
    if jax_events is not None:
        fam = reg.counter("zoo_jax_compile_seconds_total", "",
                          ("event", "stage"))
        for (event, stage), s in jax_events.items():
            fam.labels(event=event, stage=stage).inc(s)
    return reg


WARM = {"context.init": 0.5, "estimator.init": 0.25, "engine.build": 0.125,
        "engine.init_vars": 8.0, "engine.place_params": 2.0,
        "engine.opt_init": 4.0, "compile.lower": 6.0, "compile.load": 3.0,
        "compile.first_call": 1.5, "fit.fuse_probe": 64.0}
JAX = {("backend_compile", "engine.init_vars"): 5.0,
       ("backend_compile", "engine.opt_init"): 1.0,
       ("cache_retrieval", "engine.init_vars"): 4.0,   # inside the 5.0
       ("trace", "engine.init_vars"): 0.5,
       ("backend_compile", "compile.xla"): 32.0,
       ("backend_compile", "none"): 16.0}               # the reference's


def test_each_reader_is_exact_on_counters_set_by_hand():
    reg = registry_with(WARM, JAX)
    assert read("engine_build_s", reg) == 14.125
    assert read("engine_build_jax_compile_s", reg) == 6.0
    assert read("step_lower_s", reg) == 6.0
    assert read("step_load_s", reg) == 3.0
    assert read("step_first_call_s", reg) == 1.5
    # every stage but the probe
    assert read("setup_program_s", reg) == 25.375


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_without_the_family(name):
    assert read(name, MetricsRegistry()) is None
    # the family registered and no stage ever counted: a program that built
    # nothing
    assert read(name, registry_with({})) is None
    # JAX's events alone (a program that compiled outside any stage)
    assert read(name, registry_with(None, JAX)) is None


def test_a_run_that_compiled_loaded_nothing():
    cold = dict(WARM, **{"compile.xla": 40.0, "compile.save": 2.0})
    del cold["compile.load"]
    reg = registry_with(cold, {})
    assert read("step_load_s", reg) == 0.0
    assert read("step_lower_s", reg) == 6.0
    assert read("setup_program_s", reg) == 25.375 - 3.0 + 42.0
    # stages counted, none of JAX's events under the build's: 0, not nothing
    assert read("engine_build_jax_compile_s", reg) == 0.0
    assert read("engine_build_jax_compile_s", registry_with(cold)) is None


def test_a_traced_toy_run_reports_all_six(tmp_path):
    with open(TOY_JSON) as f:
        bench = json.load(f)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] in NAMES]
    assert [m["name"] for m in entries] == list(NAMES)
    for m in entries:
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("s", "lower", "program_counter", "setup_s")
        bench["per_layer"].append(dict(m, workloads=["toy_resnet.fit"]))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = spec.load_cell("toy_resnet.fit", str(path), [TOY, BENCH])
    line, out = run_toy(cell, tmp_path, traced=True)
    assert line["correct"], line["compared"]
    got = {n: line["metrics"][n]["value"] for n in NAMES}
    assert all(line["metrics"][n]["unit"] == "s" for n in NAMES)
    assert all(math.isfinite(v) and v >= 0.0 for v in got.values()), got
    assert got["engine_build_s"] > 0 and got["step_lower_s"] > 0
    assert got["step_first_call_s"] > 0
    assert got["engine_build_jax_compile_s"] <= got["engine_build_s"]
    assert got["setup_program_s"] >= (
        got["engine_build_s"] + got["step_lower_s"] + got["step_load_s"]
        + got["step_first_call_s"])
    # process totals: below everything this process has spent since the toy
    # run's own clock started only if no earlier test built anything, so the
    # sum check against ``setup_s`` is the chip runs' (PERF.md); here, only
    # that the cells of the toy benchmark as it is do not report them
    assert not set(NAMES) & {m["name"]
                             for m in toy_cell("toy_resnet.fit").per_layer}
    assert out["end_to_end"]["setup_s"] > 0
