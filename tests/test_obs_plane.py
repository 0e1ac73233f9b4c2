"""Observability plane (ISSUE 10): unified metrics registry, cross-plane
structured tracing, Prometheus exposition, Perfetto export.

The acceptance-critical properties:

* one trace id demonstrably spans estimator → engine → infeed lane →
  ckpt writer, and survives a supervisor fault-injected restart (the
  restart span carries the fault kind);
* the serving request → decode → batch → device-dispatch → respond chain
  shares the HTTP request's trace id across the aiohttp handler, the
  broker payload and the batcher thread;
* ``/metrics.prom`` serves valid Prometheus text exposition covering
  counters from ≥ 4 planes while the JSON ``/metrics`` body stays
  byte-compatible;
* a 10-step traced run exports as schema-valid Chrome/Perfetto
  ``trace_event`` JSON.
"""

import json
import threading

import numpy as np
import pytest

from analytics_zoo_tpu.obs import REGISTRY, trace
from analytics_zoo_tpu.obs.export import (parse_exposition, perfetto_trace,
                                          prometheus_text, write_perfetto)
from analytics_zoo_tpu.obs.registry import MetricsRegistry


@pytest.fixture()
def traced():
    """Arm tracing with a clean ring; disarm + clear afterwards."""
    trace.clear()
    trace.arm()
    yield trace
    trace.disarm()
    trace.clear()


def _tiny_module():
    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)[:, 0]

    return M()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("zoo_t1_events_total", "events", labelnames=("event",))
    c.labels(event="a").inc()
    c.labels(event="a").inc(2)
    c.labels(event="b").inc()
    assert c.labels(event="a").value == 3
    assert c.labels(event="b").value == 1
    # idempotent re-registration returns the SAME family
    assert reg.counter("zoo_t1_events_total",
                       labelnames=("event",)) is c
    # kind/label mismatch is an error, not a silent shadow
    with pytest.raises(ValueError):
        reg.gauge("zoo_t1_events_total")
    g = reg.gauge("zoo_t1_depth")
    g.set(5)
    g.inc(-1)
    assert g.value == 4
    h = reg.histogram("zoo_t1_lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.labels().snapshot()
    assert snap["buckets"] == [1, 2, 3] and snap["count"] == 3
    # naming rules are enforced at registration
    with pytest.raises(ValueError):
        reg.counter("Bad-Name")
    # labeled family refuses label-less use
    with pytest.raises(ValueError):
        c.inc()


def test_registry_collector_adapter_weakref():
    import gc

    reg = MetricsRegistry()

    class Stats:
        def snapshot(self):
            return {"x_s": 1.5, "n": 2, "flag": True,
                    "nested": {"bytes": 7}}

    s = Stats()
    reg.register_object("zoo_t2", s, inst="i0")
    samples = {name: v for name, labels, v in reg.collector_samples()}
    # numeric entries flattened, bools skipped, nesting joined
    assert samples == {"zoo_t2_x_s": 1.5, "zoo_t2_n": 2.0,
                       "zoo_t2_nested_bytes": 7.0}
    labels = [labels for _, labels, _ in reg.collector_samples()]
    assert all(lb == {"inst": "i0"} for lb in labels)
    del s
    gc.collect()
    assert reg.collector_samples() == []    # dead instance dropped


def test_resilience_stats_is_view_over_registry():
    from analytics_zoo_tpu.resilience.stats import STATS
    STATS.reset()
    assert STATS.snapshot() == {}           # empty until something fires
    STATS.add("fault.test_site")
    STATS.add("fault.test_site")
    STATS.add("supervisor.restarts", 1)
    snap = STATS.snapshot()
    assert snap == {"fault.test_site": 2, "supervisor.restarts": 1}
    # the same counters serve on the registry exposition
    parsed = parse_exposition(prometheus_text())
    assert parsed[
        'zoo_resilience_events_total{event="fault.test_site"}'] == 2.0
    STATS.reset()
    assert STATS.snapshot() == {}


def test_prometheus_exposition_covers_four_planes(orca_context, tmp_path):
    """After touching the infeed, ckpt, serving and resilience planes, the
    one exposition carries counters from all of them (plus the compile
    collector) and parses with the strict mini-parser."""
    from analytics_zoo_tpu.ckpt import CheckpointPlane
    from analytics_zoo_tpu.native.infeed import PipelineStats
    from analytics_zoo_tpu.resilience.stats import STATS
    from analytics_zoo_tpu.serving import ClusterServing, InMemoryBroker

    stats = PipelineStats()
    stats.add("h2d", 0.25, nbytes=1 << 20)
    plane = CheckpointPlane(str(tmp_path / "ck"))
    plane.save({"w": np.zeros(4, np.float32)}, step=0, blocking=True)

    class _Echo:
        def predict(self, x):
            return np.asarray(x)

    cs = ClusterServing(_Echo(), queue=InMemoryBroker())
    STATS.add("obs.test_marker")
    try:
        text = prometheus_text()
        parsed = parse_exposition(text)     # raises on any malformed line
        prefixes = {k.split("_")[1].split("{")[0] for k in parsed}
        assert {"infeed", "ckpt", "serving", "resilience",
                "compile"} <= prefixes, sorted(parsed)
        # the serving engine's children exist at 0 from construction
        assert any(k.startswith("zoo_serving_engine_events_total")
                   and 'event="shed_expired"' in k for k in parsed)
        # HELP/TYPE headers present for typed families
        assert "# TYPE zoo_resilience_events_total counter" in text
    finally:
        plane.close()
        cs.stop()
        STATS.reset()


# ---------------------------------------------------------------------------
# tracing core
# ---------------------------------------------------------------------------

def test_trace_disarmed_is_noop():
    trace.disarm()
    trace.clear()
    with trace.span("x", a=1) as sp:
        sp.set(b=2)             # no-op surface works
        assert trace.token() is None
        assert trace.current_trace_id() is None
    trace.record_span("y", 0.0, 1.0)
    assert trace.spans() == []


def test_span_nesting_parent_ids_and_ring_bound(traced):
    with trace.span("root") as root:
        tok = trace.token()
        with trace.span("child"):
            with trace.span("grandchild"):
                pass
    by = {s.name: s for s in trace.spans()}
    assert by["child"].parent_id == by["root"].span_id
    assert by["grandchild"].parent_id == by["child"].span_id
    assert len({s.trace_id for s in by.values()}) == 1
    assert tok == f"{by['root'].trace_id}:{by['root'].span_id}"
    # bounded ring: oldest spans evicted, process never grows
    trace.configure(capacity=16)
    try:
        for i in range(100):
            with trace.span("s", i=i):
                pass
        spans = trace.spans()
        assert len(spans) == 16
        assert spans[-1].attrs["i"] == 99
    finally:
        trace.configure(capacity=4096)


def test_cross_thread_handoff_token(traced):
    """span_under/adopt carry one trace across a worker thread, the way
    the infeed lanes and ckpt writer do."""
    out = {}

    def worker(tok):
        with trace.span_under(tok, "lane"):
            with trace.adopt(tok):
                out["adopted"] = trace.current_trace_id()

    with trace.span("root"):
        tok = trace.token()
        t = threading.Thread(target=worker, args=(tok,), daemon=True,
                             name="obs-test-worker")
        t.start()
        t.join()
    by = {s.name: s for s in trace.spans()}
    assert by["lane"].trace_id == by["root"].trace_id
    assert by["lane"].parent_id == by["root"].span_id
    assert out["adopted"] == by["root"].trace_id
    assert by["lane"].thread != by["root"].thread


# ---------------------------------------------------------------------------
# the acceptance chains
# ---------------------------------------------------------------------------

def test_one_trace_fit_to_infeed_lane_to_ckpt_writer(orca_context, tmp_path,
                                                     traced):
    """One trace id across estimator fit → epoch → engine dispatch →
    infeed H2D lane (pool thread) → ckpt writer drain (writer thread)."""
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.trigger import SeveralIteration

    rng = np.random.RandomState(0)
    est = TPUEstimator(_tiny_module(), loss="mse", optimizer="adam",
                       model_dir=str(tmp_path), seed=0,
                       config={"steps_per_dispatch": 1})
    est.fit({"x": rng.rand(256, 8).astype(np.float32),
             "y": rng.rand(256).astype(np.float32)},
            epochs=1, batch_size=32,
            checkpoint_trigger=SeveralIteration(4), verbose=False)
    est.shutdown()

    by = {}
    for s in trace.spans():
        by.setdefault(s.name, []).append(s)
    (fit_span,) = by["fit"]
    for name in ("epoch", "engine.dispatch", "infeed.assemble",
                 "infeed.h2d", "ckpt.write"):
        assert any(s.trace_id == fit_span.trace_id for s in by[name]), name
    # the lane + writer spans really ran on other threads
    assert any(s.thread != fit_span.thread for s in by["infeed.h2d"])
    assert any(s.thread != fit_span.thread for s in by["ckpt.write"])
    # dispatch spans are step-indexed (the Perfetto per-step segments)
    steps = sorted(s.attrs.get("step") for s in by["engine.dispatch"])
    assert steps == list(range(len(steps)))


def test_supervisor_restart_span_carries_fault_kind(orca_context, tmp_path,
                                                    traced):
    """The trace survives a fault-injected supervisor restart: the restart
    span is annotated with the classified fault kind and shares the
    supervised run's trace id with the segments before AND after it."""
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.resilience import TrainingSupervisor, faults

    rng = np.random.RandomState(0)
    data = {"x": rng.rand(64, 8).astype(np.float32),
            "y": rng.rand(64).astype(np.float32)}
    sup = TrainingSupervisor(
        lambda: TPUEstimator(_tiny_module(), loss="mse", optimizer="adam",
                             model_dir=str(tmp_path), seed=0,
                             config={"steps_per_dispatch": 1}),
        model_dir=str(tmp_path), max_restarts=2)
    sup.retry_policy.base_delay_s = 0.01
    with faults.inject("engine.dispatch", count=1, skip=3):
        report = sup.fit(dict(data), epochs=2, batch_size=32)
    sup.estimator.shutdown()
    assert report["restarts"] == 1 and report["completed"]

    by = {}
    for s in trace.spans():
        by.setdefault(s.name, []).append(s)
    (sup_span,) = by["supervisor.fit"]
    (restart,) = by["supervisor.restart"]
    assert restart.trace_id == sup_span.trace_id
    assert restart.attrs["kind"] == "crash"
    assert restart.attrs["cause"] == "InjectedFault"
    # segment fits (worker threads, across the restart) stay on the trace
    fit_spans = by["fit"]
    assert len(fit_spans) >= 2
    assert all(s.trace_id == sup_span.trace_id for s in fit_spans)
    assert all(s.thread != sup_span.thread for s in fit_spans)


def test_serving_request_to_dispatch_chain(orca_context, traced):
    """request → decode → batch → device-dispatch → respond under the
    aiohttp frontend: the request span's token rides the payload meta to
    the batcher thread, so the whole chain shares one trace id."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from analytics_zoo_tpu.serving import ClusterServing, InMemoryBroker
    from analytics_zoo_tpu.serving.http_frontend import create_app

    class _Echo:
        def predict(self, x):
            return np.asarray(x) * 2.0

    broker = InMemoryBroker()
    cs = ClusterServing(_Echo(), queue=broker, batch_size=4,
                        batch_timeout_ms=10).start()
    try:
        async def run():
            app = create_app(queue=broker, timeout_s=10.0, serving=cs)
            async with TestClient(TestServer(app)) as client:
                r = await client.post(
                    "/predict", json={"instances": [[1.0, 2.0]]})
                body = await r.json()
                prom = await client.get("/metrics.prom")
                return r.status, body, await prom.text(), prom.status

        status, body, prom_text, prom_status = \
            asyncio.new_event_loop().run_until_complete(run())
        assert status == 200
        assert body["predictions"] == [[2.0, 4.0]]
        assert prom_status == 200
        parse_exposition(prom_text)     # valid exposition over HTTP
    finally:
        cs.stop()

    by = {}
    for s in trace.spans():
        by.setdefault(s.name, []).append(s)
    (req,) = by["serving.request"]
    for name in ("serving.decode", "serving.batch", "serving.dispatch",
                 "serving.respond"):
        chained = [s for s in by[name] if s.trace_id == req.trace_id]
        assert chained, name
        # the engine stages ran on the batcher thread, not the server's
        assert all(s.thread != req.thread for s in chained), name


def test_metrics_json_stays_byte_compatible(orca_context, traced):
    """The JSON /metrics body keeps its exact keys/types with the counters
    now registry-backed: per-app ints starting at 0, 429s counted."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from analytics_zoo_tpu.serving import InMemoryBroker
    from analytics_zoo_tpu.serving.http_frontend import create_app

    broker = InMemoryBroker()

    async def run():
        app = create_app(queue=broker, timeout_s=5.0, max_pending=0)
        async with TestClient(TestServer(app)) as client:
            m0 = await (await client.get("/metrics")).json()
            r = await client.post("/predict",
                                  json={"instances": [[1.0]]})
            m1 = await (await client.get("/metrics")).json()
            return m0, r.status, m1

    m0, status, m1 = asyncio.new_event_loop().run_until_complete(run())
    assert m0["resilience"]["rejected_429"] == 0        # fresh app = 0
    assert m0["resilience"]["expired_results"] == 0
    assert isinstance(m0["resilience"]["rejected_429"], int)
    assert status == 429
    assert m1["resilience"]["rejected_429"] == 1
    assert "pending" in m0 and "compile" in m0


# ---------------------------------------------------------------------------
# exporters + CLI + knobs + event log
# ---------------------------------------------------------------------------

def test_perfetto_export_schema_valid(orca_context, tmp_path, traced):
    """A 10-step traced run exports as schema-valid trace_event JSON."""
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator

    rng = np.random.RandomState(0)
    est = TPUEstimator(_tiny_module(), loss="mse", optimizer="adam",
                       seed=0, config={"steps_per_dispatch": 1})
    est.fit({"x": rng.rand(320, 8).astype(np.float32),
             "y": rng.rand(320).astype(np.float32)},
            epochs=1, batch_size=32, verbose=False)

    path = write_perfetto(str(tmp_path / "trace.json"))
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    names = set()
    for e in events:
        assert e["ph"] in ("X", "M", "C")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert e["args"]["trace"] and e["args"]["span"]
            names.add(e["name"])
    assert {"fit", "epoch", "engine.dispatch"} <= names
    # 10 steps → 10 step-indexed dispatch segments
    dispatch = [e for e in events
                if e["ph"] == "X" and e["name"] == "engine.dispatch"]
    assert len(dispatch) == 10
    assert sorted(e["args"]["step"] for e in dispatch) == list(range(10))
    # thread-name metadata labels every track that recorded a span
    tids = {e["tid"] for e in events if e["ph"] == "X"}
    named = {e["tid"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tids <= named


def test_zoo_metrics_dump_cli(capsys):
    from analytics_zoo_tpu.obs import export
    assert export.main(["dump"]) == 0
    out = capsys.readouterr().out
    parse_exposition(out)
    assert export.main(["dump", "--json"]) == 0
    json.loads(capsys.readouterr().out)


def test_obs_knobs_registered():
    from analytics_zoo_tpu.common import knobs
    for name in ("ZOO_OBS", "ZOO_TRACE", "ZOO_TRACE_RING",
                 "ZOO_TRACE_PERFETTO"):
        assert knobs.is_registered(name), name
        assert f"`{name}`" in knobs.markdown_table()
    assert knobs.get("ZOO_OBS") is True
    assert knobs.get("ZOO_TRACE") is False
    assert knobs.get("ZOO_TRACE_RING") == 4096


def test_event_log_stamps_trace_id(tmp_path, traced):
    from analytics_zoo_tpu.automl.scheduler.events import EventLog
    log = EventLog(str(tmp_path))
    with trace.span("trial", trial="t1"):
        tid = trace.current_trace_id()
        log.emit("trial_start", trial="t1")
    log.emit("untraced_event")          # outside any span: no trace field
    log.close()
    lines = [json.loads(ln) for ln in
             open(str(tmp_path / "study_events.jsonl"), encoding="utf-8")]
    assert lines[0]["trace"] == tid
    assert "trace" not in lines[1]


def test_trial_events_carry_per_trial_trace_ids(orca_context, tmp_path,
                                                traced):
    """Two scheduled trials → two distinct trace ids in
    study_events.jsonl, consistent within each trial's events."""
    from analytics_zoo_tpu.automl.scheduler.runtime import TrialRuntime
    from analytics_zoo_tpu.automl.search.search_engine import Trial

    class _Model:
        def __init__(self, config, mesh):
            self.config = config

        def fit_eval(self, data, validation_data, epochs, metric):
            return float(self.config["x"]), \
                {metric: float(self.config["x"])}, None

    trials = [Trial(i, {"x": 1.0 + i}) for i in range(2)]
    rt = TrialRuntime(trials, _Model, data=None, metric="score",
                      metric_mode="min", max_t=1, logs_dir=str(tmp_path),
                      max_concurrent=1)
    rt.run()
    lines = [json.loads(ln) for ln in
             open(str(tmp_path / "study_events.jsonl"), encoding="utf-8")]
    per_trial = {}
    for rec in lines:
        if "trial" in rec and "trace" in rec:
            per_trial.setdefault(rec["trial"], set()).add(rec["trace"])
    assert len(per_trial) == 2
    # one consistent trace id per trial, distinct across trials
    assert all(len(tids) == 1 for tids in per_trial.values())
    assert len(set().union(*per_trial.values())) == 2


# ---------------------------------------------------------------------------
# ISSUE 33: spans live under a JAX profiler session, and where fit loses time
# ---------------------------------------------------------------------------

FIT_SPANS = ("fit", "fit.prepare", "epoch", "infeed.first_batch",
             "infeed.wait", "infeed.assemble", "infeed.h2d",
             "engine.dispatch", "epoch.open_ahead", "epoch.sync")


def _tiny_fit(**fit_kwargs):
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    rng = np.random.RandomState(0)
    est = TPUEstimator(_tiny_module(), loss="mse", optimizer="adam", seed=0,
                       config={"steps_per_dispatch": 1})
    est.fit({"x": rng.rand(128, 8).astype(np.float32),
             "y": rng.rand(128).astype(np.float32)},
            epochs=2, batch_size=32, verbose=False, **fit_kwargs)
    return est


@pytest.fixture(scope="module")
def profiled_fit(tmp_path_factory):
    """One tiny fit, unarmed, inside a profiler session: the ring's spans,
    the session's trace file, and what ``span()`` returned before and after."""
    import glob

    import jax

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.common import context as ctx_mod
    live = ctx_mod._current
    if live is None or live._stopped:
        init_orca_context("cpu-sim", mesh_axes={"dp": -1})
    trace.disarm()
    trace.clear()
    _tiny_fit().shutdown()              # no session, unarmed: compiles too
    out = {"before": trace.spans(), "noop_before": trace.span("x")}
    logdir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(logdir)
    try:
        out["enabled_inside"] = trace.enabled()
        _tiny_fit().shutdown()
    finally:
        jax.profiler.stop_trace()
    out["noop_after"] = trace.span("x")
    out["spans"] = trace.spans()
    (out["xplane"],) = glob.glob(
        logdir + "/plugins/profile/*/*.xplane.pb")
    trace.clear()
    return out


def _by_name(spans):
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    return by


def test_profiler_session_makes_fit_spans_live(profiled_fit):
    """(a) nothing armed: the ring stays empty through a fit, fills inside
    ``start_trace`` … ``stop_trace`` under one trace id with the catalogue's
    parent links, and is off again after the session."""
    with trace.span("x") as noop:
        pass
    assert profiled_fit["before"] == []
    assert profiled_fit["noop_before"] is noop
    assert profiled_fit["noop_after"] is noop
    assert profiled_fit["enabled_inside"] and not trace.enabled()
    spans = profiled_fit["spans"]
    by = _by_name(spans)
    assert set(FIT_SPANS) <= set(by)
    (fit,) = by["fit"]
    assert fit.parent_id is None and fit.attrs["steps"] == 8
    # the estimator was made inside the session, so its set-up stage is a
    # span as well: a root of its own, closed before ``fit`` opened; the
    # build's and the first call's stages hang under the fit call's spans
    (init,) = by["estimator.init"]
    assert init.parent_id is None and init.t1 <= fit.t0
    assert {s.trace_id for s in spans} == {fit.trace_id, init.trace_id}
    by_id = {s.span_id: s for s in spans}
    parent = {"fit.prepare": "fit", "epoch": "fit", "infeed.wait": "epoch",
              "engine.dispatch": "epoch", "epoch.open_ahead": "epoch",
              "epoch.sync": "epoch"}
    for name, above in parent.items():
        assert all(by_id[s.parent_id].name == above for s in by[name]), name
    for name in ("fit.prepare", "epoch", "infeed.first_batch", "infeed.wait",
                 "engine.dispatch", "epoch.open_ahead", "epoch.sync"):
        assert all(s.thread == fit.thread for s in by[name]), name
    for name in ("infeed.assemble", "infeed.h2d"):
        assert all(s.thread != fit.thread for s in by[name]), name
    assert len(by["epoch"]) == len(by["infeed.first_batch"]) == \
        len(by["epoch.sync"]) == 2
    assert len(by["engine.dispatch"]) == len(by["infeed.h2d"]) == 8
    # a pump's spans hang where the pump was started: the call's first epoch
    # starts its own, every later one (N - 1 of N) is opened ahead, before the
    # sync of the epoch before it
    ep0, ep1 = sorted(by["epoch"], key=lambda s: s.attrs["epoch"])
    (ahead,) = by["epoch.open_ahead"]
    (sync0,) = [s for s in by["epoch.sync"] if s.parent_id == ep0.span_id]
    assert ahead.parent_id == ep0.span_id and ahead.attrs["epoch"] == 1
    assert ahead.t1 <= sync0.t0 <= ep1.t0
    for name, each in (("infeed.first_batch", 1), ("infeed.assemble", 4),
                       ("infeed.h2d", 4)):
        assert sorted(s.parent_id for s in by[name]) == sorted(
            [ep0.span_id] * each + [ahead.span_id] * each), name


def test_profiler_trace_holds_zoo_annotations(profiled_fit):
    """(b) the same spans are in the session's ``.xplane.pb`` as ``zoo:``
    annotations on a host plane, ``engine.dispatch`` inside ``fit`` in time."""
    import jax
    data = jax.profiler.ProfileData.from_file(profiled_fit["xplane"])
    found = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("zoo:"):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert {"zoo:" + n for n in FIT_SPANS} <= set(found)
    ((fit_lo, fit_hi),) = found["zoo:fit"]
    assert len(found["zoo:engine.dispatch"]) == 8
    assert all(fit_lo <= lo and hi <= fit_hi
               for lo, hi in found["zoo:engine.dispatch"])
    # the set-up stages that ran inside the session are annotations too:
    # the estimator made before ``fit``, the build and the step's first call
    # (found in the shared store: the fixture's first fit compiled it)
    # inside it
    ((init_lo, init_hi),) = found["zoo:estimator.init"]
    assert init_hi <= fit_lo
    for name in BUILD_STAGES + ("compile.first_call", "compile.lower"):
        assert all(fit_lo <= lo and hi <= fit_hi
                   for lo, hi in found["zoo:" + name]), name


# the set-up stages of an engine's build: spans inside the ``fit.prepare``
# of an estimator's first call, where the engine is built
BUILD_STAGES = ("engine.build", "engine.init_vars", "engine.place_params",
                "engine.opt_init")


def test_fit_prepare_span_and_ready_batch_assembly(profiled_fit):
    """(c) ``fit.prepare`` opens with ``fit`` and closes before the first
    epoch, the engine's build inside it; a factory of ready batches (the
    ImageNetPipeline contract) leaves ``infeed.assemble`` spans too, from
    the producer thread."""
    from analytics_zoo_tpu.native.infeed import InfeedPump
    by = _by_name(profiled_fit["spans"])
    (fit,), (prep,) = by["fit"], by["fit.prepare"]
    others = [s for s in profiled_fit["spans"]
              if s.name not in ("fit", "fit.prepare", "estimator.init")
              + BUILD_STAGES]
    assert fit.t0 <= prep.t0 <= prep.t1 <= min(s.t0 for s in others)
    for name in BUILD_STAGES:
        (built,) = by[name]
        assert prep.t0 <= built.t0 <= built.t1 <= prep.t1
    assert prep.t1 <= min(s.t0 for s in by["epoch"])

    batches = [np.full((4, 2), i, np.float32) for i in range(5)]
    trace.clear()
    with trace.tracing():
        with trace.span("epoch") as ep:
            got = [np.asarray(b) for b in InfeedPump(lambda: iter(batches))]
    assert [int(g[0, 0]) for g in got] == list(range(5))
    spans = trace.drain()
    asm = [s for s in spans if s.name == "infeed.assemble"]
    assert len(asm) == 5
    assert all(s.parent_id == ep.span_id and s.trace_id == ep.trace_id
               and s.thread_name == "zoo-infeed-pump" for s in asm)


def test_fit_on_built_engine_prepares_without_a_batch(orca_context):
    """(c') every ``fit`` call but an estimator's first finds its engine
    built: its ``fit.prepare`` assembles nothing and puts nothing (no span
    of any thread starts inside it, the pipeline's counters grow by the
    epochs' batches alone), where the first call took one sample and built
    the engine (the build's set-up stages, and nothing else)."""
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    rng = np.random.RandomState(0)
    data = {"x": rng.rand(128, 8).astype(np.float32),
            "y": rng.rand(128).astype(np.float32)}
    est = TPUEstimator(_tiny_module(), loss="mse", optimizer="adam", seed=0,
                       config={"steps_per_dispatch": 1})
    grew = []
    for built_here in (BUILD_STAGES, ()):
        before = est.data_pipeline_stats()
        trace.clear()
        with trace.tracing():
            est.fit(data, epochs=2, batch_size=32, verbose=False)
            spans = trace.drain()
        after = est.data_pipeline_stats()
        grew.append({k: after[k] - before[k] for k in
                     ("assemble_n", "h2d_n", "first_batch_n",
                      "open_ahead_n")})
        (prep,) = [s for s in spans if s.name == "fit.prepare"]
        inside = [s.name for s in spans
                  if s is not prep and prep.t0 <= s.t0 <= prep.t1]
        assert sorted(inside) == sorted(built_here)
    est.shutdown()
    assert grew[0] == {"assemble_n": 9, "h2d_n": 9, "first_batch_n": 2,
                       "open_ahead_n": 1}
    assert grew[1] == {"assemble_n": 8, "h2d_n": 8, "first_batch_n": 2,
                       "open_ahead_n": 1}


def test_pump_counts_each_epochs_first_batch_apart_from_stalls():
    """(d) the wait for an epoch's first batch is a stage of its own;
    ``stall`` keeps its meaning (every later get that delivered a batch)."""
    from analytics_zoo_tpu.native.infeed import InfeedPump
    epochs, n = 3, 5
    pump = InfeedPump(lambda: (np.zeros((4, 2), np.float32)
                               for _ in range(n)))
    for _ in range(epochs):
        assert sum(1 for _ in pump) == n
    snap = pump.stats.snapshot()
    assert snap["first_batch_n"] == epochs
    assert snap["stall_n"] == epochs * (n - 1)
    assert snap["first_batch_s"] > 0
    assert snap["h2d_n"] == snap["assemble_n"] == epochs * n


def test_h2d_span_and_counter_share_the_enqueue_boundary():
    """(e) ``device_put`` returns at the enqueue; the ``infeed.h2d`` span and
    ``h2d_s`` both end there, live or off (one code path, no wait of a lane
    for the device), and a put that fails surfaces at the consumer."""
    import time

    from analytics_zoo_tpu.native import infeed
    order = []

    def fake_put(host):
        order.append(("put", time.perf_counter()))
        return host

    def transfer():
        pump = infeed.InfeedPump(lambda: iter(()), device_put=fake_put)
        add = pump.stats.add
        pump.stats.add = lambda stage, *a, **k: (
            order.append((stage, time.perf_counter())), add(stage, *a, **k))
        pump._transfer(np.zeros((4, 2), np.float32))
        return pump

    trace.disarm()
    trace.clear()
    transfer()
    assert [o[0] for o in order] == ["put", "h2d"] and trace.spans() == []
    del order[:]
    with trace.tracing():
        pump = transfer()
    assert [o[0] for o in order] == ["put", "h2d"]
    (span,) = [s for s in trace.drain() if s.name == "infeed.h2d"]
    assert span.t0 <= order[0][1] <= order[1][1] <= span.t1
    assert pump.stats.snapshot()["h2d_n"] == 1

    def broken_put(host):
        raise OSError("link down")

    batches = [np.zeros((4, 2), np.float32)] * 3
    with pytest.raises(OSError, match="link down"):
        list(infeed.InfeedPump(lambda: iter(batches), device_put=broken_put))


def test_span_ids_never_read_os_urandom(monkeypatch):
    """A live span site draws its ids without a system call: ``os.urandom``
    (``uuid4``) releases the GIL, and beside CPU-bound threads the training
    loop then waits milliseconds at every site to get it back."""
    import os

    def no_syscall(n):
        raise AssertionError("a span id read os.urandom")

    monkeypatch.setattr(os, "urandom", no_syscall)
    trace.clear()
    with trace.tracing():
        for _ in range(2000):
            with trace.span("x"):
                pass
        trace.record_span("y", 0.0, 1.0)
    spans = trace.drain()
    ids = {s.span_id for s in spans} | {s.trace_id for s in spans}
    assert len(ids) == 2 * 2001
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)


def test_train_step_ops_carry_stable_scope_names(orca_context):
    """(f) forward / backward / optimizer can be told apart in the lowered
    step (and so in a device trace) whatever XLA fuses."""
    import re

    import jax

    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.utils import Batch
    est = TPUEstimator(_tiny_module(), loss="mse", optimizer="adam", seed=0)
    x = np.zeros((32, 8), np.float32)
    est.engine.build((x,))
    batch = Batch(x=(x,), y=(np.zeros(32, np.float32),), w=None)
    for step in (est.engine._train_step, est.engine._train_multi_step):
        args = est.engine.train_step_args(batch)
        if step == est.engine._train_multi_step:
            args = args[:4] + tuple(
                jax.tree.map(lambda a: a[None], a) for a in args[4:])
        text = jax.jit(step).lower(*args).as_text(debug_info=True)
        # under value_and_grad JAX names the forward ops `jvp(forward)` and
        # their transposes, the backward, `transpose(jvp(forward))`
        for scope in ("jvp(forward)", "transpose(jvp(forward))",
                      "optimizer"):
            # a scope opens a location's name, or follows the jit's
            assert re.search('["/]' + re.escape(scope) + "/", text), scope
    est.shutdown()
