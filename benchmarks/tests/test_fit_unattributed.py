"""The reader of the program's own spans (PR 33), ``fit_unattributed_pct
.train``: exact on span lists built by hand (a hole inside an ``epoch`` counts,
a lane thread's span covers nothing), nothing to read where there is no
``fit`` span, the last ``fit`` call winning over an earlier one; and one
traced toy run whose result line carries it, registered with an appended entry
in a copied ``BENCHMARK.json`` and a directory of its own, no file of the
benchmark edited."""

import json
import os
import shutil

import pytest

from analytics_zoo_tpu.obs.trace import Span
from harness import spec
from test_harness_cpu import BENCH, TOY, TOY_JSON, run_toy, toy_cell

NAME = "fit_unattributed_pct.train"
MAIN, LANE = 1, 2


def read(spans):
    return spec.load_py(os.path.join(
        BENCH, "layer_metrics", f"{NAME}.py")).read({"spans": spans})


def sp(name, ident, parent, t0, t1, trace="t", thread=MAIN):
    return Span(name, trace, ident, parent, t0, t1, thread, str(thread), {})


def one_call(trace="t", at=0.0, scale=1.0):
    """A 1 s ``fit`` call of two epochs. Outside every span: 20 ms after the
    prepare, 10 ms between the epochs, 70 ms after the last. Inside the second
    epoch and under none of its leaves: 30 ms between two dispatches and 50 ms
    after the sync (the pump's close)."""
    def t(x):
        return at + scale * x
    f = trace + "fit"
    e0, e1 = trace + "e0", trace + "e1"
    return [
        sp("fit.prepare", trace + "p", f, t(0.0), t(0.100), trace),
        sp("infeed.first_batch", trace + "fb0", e0, t(0.120), t(0.180), trace),
        sp("infeed.h2d", trace + "h0", e0, t(0.130), t(0.150), trace, LANE),
        sp("engine.dispatch", trace + "d0", e0, t(0.180), t(0.300), trace),
        sp("epoch.sync", trace + "s0", e0, t(0.300), t(0.520), trace),
        sp("epoch", e0, f, t(0.120), t(0.520), trace),
        sp("infeed.first_batch", trace + "fb1", e1, t(0.530), t(0.630), trace),
        sp("engine.dispatch", trace + "d1", e1, t(0.630), t(0.700), trace),
        # a lane's span over the loop's hole, and one that outlives the
        # call: no part of the loop's thread
        sp("infeed.h2d", trace + "h1", e1, t(0.690), t(0.740), trace, LANE),
        sp("infeed.h2d", trace + "h2", e1, t(0.900), t(1.200), trace, LANE),
        sp("infeed.wait", trace + "w1", e1, t(0.730), t(0.750), trace),
        sp("engine.dispatch", trace + "d2", e1, t(0.750), t(0.800), trace),
        sp("epoch.sync", trace + "s1", e1, t(0.800), t(0.880), trace),
        sp("epoch", e1, f, t(0.530), t(0.930), trace),
        sp("fit", f, None, t(0.0), t(1.0), trace),
    ]


def test_the_reader_is_exact_on_a_hand_built_call():
    # 20 + 10 + 70 ms outside the epochs, 30 + 50 ms inside the second
    assert read(one_call()) == pytest.approx(18.0)
    # an epoch covers nothing by itself: without its sync span, 220 ms more
    no_sync = [s for s in one_call() if s.span_id != "ts0"]
    assert read(no_sync) == pytest.approx(40.0)


def test_nothing_to_read_without_a_fit_span():
    assert read([]) is None
    assert read([s for s in one_call() if s.name != "fit"]) is None
    assert read([sp("fit", "f", None, 0.0, 1.0)]) == pytest.approx(100.0)
    assert read([sp("fit", "f", None, 1.0, 1.0)]) is None


def test_the_last_fit_call_is_the_one_read():
    settle = [s for s in one_call("a", at=0.0, scale=3.0)
              if s.name != "fit.prepare"]           # the profiler settles
    later_orphan = [sp("engine.dispatch", "x", "gone", 10.0, 11.0, "c")]
    assert read(settle) == pytest.approx(28.0)
    assert read(settle + one_call("b", at=10.0) + later_orphan) == \
        pytest.approx(18.0)
    assert read(one_call("b", at=10.0) + settle) == pytest.approx(28.0)
    # same trace id: another call's spans are not its descendants
    same_trace = one_call("a") + [
        sp("fit", "f2", None, 10.0, 11.0, "a"),
        sp("fit.prepare", "p2", "f2", 10.0, 10.25, "a")]
    assert read(same_trace) == pytest.approx(75.0)


def test_a_traced_toy_run_reports_it(tmp_path):
    extra = tmp_path / "extra"
    (extra / "layer_metrics").mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "layer_metrics", f"{NAME}.py"),
                extra / "layer_metrics" / f"{NAME}.py")
    with open(TOY_JSON) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "estimator",
        "moves": "train_samples_per_s_per_chip",
        "workloads": ["toy_resnet.fit"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    # the copy is found first: the benchmark's own directory comes last
    cell = spec.load_cell("toy_resnet.fit", str(path),
                          [str(extra), TOY, BENCH])
    line, out = run_toy(cell, tmp_path, traced=True)
    assert line["correct"], line["compared"]
    m = line["metrics"][NAME]
    assert m["unit"] == "%" and 0.0 < m["value"] < 100.0
    # the ring holds the session's two fit calls and nothing later
    from analytics_zoo_tpu.obs import trace
    fits = [s for s in trace.spans() if s.name == "fit"]
    assert [s.attrs["steps"] for s in fits] == [
        2, out["facts"]["traced"]["steps"]]
    # the cells of the toy benchmark as it is do not report it
    assert NAME not in {m["name"]
                        for m in toy_cell("toy_resnet.fit").per_layer}
