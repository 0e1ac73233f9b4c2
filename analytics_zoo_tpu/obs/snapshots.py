"""Per-plane tier-1 snapshot lines — the one codepath behind
``zoo-metrics snapshot <plane>`` and ``scripts/run_tier1.sh``.

Each function runs a tiny CPU workload through the real production path of
one plane and prints a single ``NAME=<json>`` line (``TRANSFER_PLANE=``,
``CKPT_PLANE=``, ``SHARDING_PLANE=``, ``RESILIENCE=``,
``SHM=``, ``ANALYSIS=``, ``OBS=``). These used to live as five bespoke ``python - <<EOF`` heredocs
inside run_tier1.sh; the script now loops over
``python -m analytics_zoo_tpu.obs snapshot <plane>`` so the
snapshot logic is importable, testable and shared with the CLI.

One process per plane (the sharding/analysis snapshots need the 8-device
simulated mesh, which must be configured before the JAX backend first
initializes — :func:`_ensure_sim_devices` appends the XLA flag when the
caller has not)."""

from __future__ import annotations

import json
import os
import sys
import tempfile
from typing import Dict

__all__ = ["run", "PLANES"]


def _emit(label: str, payload: Dict) -> int:
    print(label + "=" + json.dumps(payload))
    return 0


def _ensure_sim_devices(n: int = 8):
    """Force the n-device virtual CPU mesh. Must run before the first JAX
    backend initialization (importing jax is fine; creating devices is
    not) — the CLI entry satisfies that."""
    # strip-then-append (same as bench.py's child env): an ambient
    # =2 left over from other tests must not shrink the documented
    # 8-dev mesh the sharding/analysis snapshots assume
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def snapshot_transfer() -> int:
    """Per-stage MB/s + transfer_limited verdict from a tiny CPU fit
    through the production pump."""
    import flax.linen as nn
    import numpy as np

    from .. import init_orca_context
    from ..orca.learn.estimator import TPUEstimator
    from ..orca.learn.prologue import BatchPrologue, image_normalize

    init_orca_context("local")

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(x.reshape((x.shape[0], -1)))

    rng = np.random.RandomState(0)
    est = TPUEstimator(M(), loss="sparse_categorical_crossentropy",
                       optimizer="adam", config={"steps_per_dispatch": 1},
                       prologue=BatchPrologue(x=(image_normalize(),)))
    est.fit({"x": rng.randint(0, 256, (256, 8, 8, 3), np.uint8),
             "y": rng.randint(0, 4, 256).astype(np.int32)},
            epochs=1, batch_size=32, verbose=False)
    snap = est.data_pipeline_stats()
    keys = ("assemble_MBps", "h2d_MBps", "h2d_bytes", "lanes",
            "transfer_limited")
    return _emit("TRANSFER_PLANE", {k: snap[k] for k in keys if k in snap})


def snapshot_ckpt() -> int:
    """Async save latency (on-loop stall vs hidden write) + dedup ratio
    from a tiny fit checkpointing through the plane."""
    import flax.linen as nn
    import numpy as np

    from .. import init_orca_context
    from ..orca.learn.estimator import TPUEstimator
    from ..orca.learn.trigger import SeveralIteration

    init_orca_context("local")

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as d:
        est = TPUEstimator(M(), loss="mse", optimizer="adam", model_dir=d,
                           config={"steps_per_dispatch": 1})
        est.fit({"x": rng.rand(256, 8).astype(np.float32),
                 "y": rng.rand(256).astype(np.float32)},
                epochs=2, batch_size=32,
                checkpoint_trigger=SeveralIteration(4), verbose=False)
        snap = est.data_pipeline_stats().get("ckpt", {})
        est.shutdown()
    keys = ("saves", "stall_s", "hidden_s", "write_s", "stall_frac",
            "dedup_ratio", "bytes_written", "bytes_deduped")
    return _emit("CKPT_PLANE", {k: snap[k] for k in keys if k in snap})


def snapshot_sharding() -> int:
    """The sharding plane (PR 17) on the 8-device simulated fsdp×tp mesh:
    a small fit with the canonical SpecLayout — fsdp flat-vector buckets,
    per-device param+optimizer bytes vs the full state, tp axis width —
    plus a served predict from the canonical checkpoint params through a
    sharded InferenceModel, checked bit-identical to the replicated
    layout (SGD: fsdp gathers and output-dim splits preserve elementwise
    order)."""
    _ensure_sim_devices()
    import flax.linen as nn
    import jax
    import numpy as np

    from .. import init_orca_context
    from ..orca.learn.estimator import TPUEstimator
    from ..parallel.mesh import create_mesh
    from ..parallel.sharding import SpecLayout
    from ..pipeline.inference.inference_model import InferenceModel

    init_orca_context("cpu-sim", mesh_axes={"dp": 1, "fsdp": 4, "tp": 2})
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(64)(x))
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    data = {"x": rng.rand(256, 8).astype(np.float32),
            "y": rng.rand(256).astype(np.float32)}

    def run(sharding):
        est = TPUEstimator(M(), loss="mse", optimizer="sgd", seed=0,
                           mesh=mesh, config={"steps_per_dispatch": 1},
                           sharding=sharding)
        stats = est.fit(dict(data), epochs=1, batch_size=32, verbose=False)
        return est, [s["train_loss"] for s in stats]

    est, losses = run(SpecLayout())
    est_r, losses_r = run(False)
    snap = est.engine.sharding_snapshot()
    full = sum(int(l.nbytes) for l in
               jax.tree.leaves(est.engine.params)
               + jax.tree.leaves(est.engine.opt_state))
    params = est.engine.get_state()["params"]
    params_r = est_r.engine.get_state()["params"]
    xq = rng.rand(16, 8).astype(np.float32)
    ps = InferenceModel(mesh=mesh, sharding=SpecLayout()).load_jax(
        M(), {"params": params}).predict(xq)
    pr = InferenceModel(mesh=mesh).load_jax(
        M(), {"params": params_r}).predict(xq)
    fsdp = snap.get("fsdp", {})
    return _emit("SHARDING_PLANE", {
        "axes": snap["axes"],
        "tp_axis_size": snap["tp_axis_size"],
        "buckets": fsdp.get("buckets"),
        "ridden_leaves": fsdp.get("ridden_leaves"),
        "held_leaves": fsdp.get("held_leaves"),
        "gather_shard_bytes_per_sweep":
            fsdp.get("gather_shard_bytes_per_sweep"),
        "full_state_bytes": full,
        "per_device_state_bytes": snap.get("per_device_state_bytes"),
        "train_bit_identical": bool(losses == losses_r),
        "serve_bit_identical": bool(
            (np.asarray(ps) == np.asarray(pr)).all())})


def snapshot_resilience() -> int:
    """One injected mid-fit fault through the training supervisor + a
    shed/breaker pass through the serving engine."""
    import time

    import flax.linen as nn
    import numpy as np

    from .. import init_orca_context
    from ..orca.learn.estimator import TPUEstimator
    from ..resilience import TrainingSupervisor, faults
    from ..serving import ClusterServing, InMemoryBroker
    from ..serving.codecs import encode_payload

    init_orca_context("local")

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    data = {"x": rng.rand(64, 8).astype(np.float32),
            "y": rng.rand(64).astype(np.float32)}
    with tempfile.TemporaryDirectory() as d:
        sup = TrainingSupervisor(
            lambda: TPUEstimator(M(), loss="mse", optimizer="adam",
                                 model_dir=d, seed=0,
                                 config={"steps_per_dispatch": 1}),
            model_dir=d, max_restarts=2)
        sup.retry_policy.base_delay_s = 0.05
        with faults.inject("engine.dispatch", count=1, skip=3):
            report = sup.fit(dict(data), epochs=2, batch_size=32)
        sup.estimator.shutdown()

    class _Echo:
        def predict(self, x):
            return np.asarray(x)

    broker = InMemoryBroker()
    cs = ClusterServing(_Echo(), queue=broker, batch_size=4)
    for i in range(2):
        broker.enqueue(f"x{i}", encode_payload(
            np.ones(2, np.float32), meta={"deadline": time.time() - 1}))
    for i in range(2):
        broker.enqueue(f"l{i}", encode_payload(
            np.ones(2, np.float32), meta={"deadline": time.time() + 30}))
    cs.start()
    for i in range(2):
        broker.get_result(f"l{i}", 10.0)
        broker.get_result(f"x{i}", 10.0)
    res = cs.metrics()["resilience"]
    cs.drain(timeout_s=10.0)
    return _emit("RESILIENCE", {
        "restarts": report["restarts"], "hangs": report["hangs"],
        "crashes": report["crashes"],
        "steps_replayed": report["steps_replayed"],
        "downtime_s": round(report["downtime_s"], 3),
        "bit_exact_resume": report["completed"],
        "shed_expired": res["shed_expired"],
        "shed_open": res["shed_open"],
        "breaker_state": res["breaker"]["state"]})


def snapshot_serving() -> int:
    """Two models multiplexed through the continuous deadline-aware batch
    former (no JAX needed — host-side toy models keep this leg at
    milliseconds): records served per model, expired-request sheds, and
    the ``zoo_serving_*`` metric families the engine registers so
    ``zoo-metrics`` lists them."""
    import time

    import numpy as np

    from ..serving import ClusterServing, InMemoryBroker, ModelMultiplexer
    from ..serving.codecs import encode_payload
    from .registry import REGISTRY

    class _Scale:
        def __init__(self, k):
            self.k = k

        def predict(self, x):
            return np.asarray(x) * self.k

    mux = (ModelMultiplexer()
           .add_model("double", _Scale(2.0))
           .add_model("half", _Scale(0.5)))
    broker = InMemoryBroker()
    cs = ClusterServing(mux, queue=broker, batch_size=8, slack_ms=10.0,
                        max_inflight=64)
    n_live, n_expired = 24, 4
    for i in range(n_expired):
        broker.enqueue(f"x{i}", encode_payload(
            np.ones(4, np.float32), meta={"deadline": time.time() - 1}))
    for i in range(n_live):
        broker.enqueue(f"l{i}", encode_payload(
            np.ones(4, np.float32),
            meta={"model": ("double", "half")[i % 2],
                  "deadline": time.time() + 30}))
    cs.start()
    ok = 0
    for i in range(n_live):
        raw = broker.get_result(f"l{i}", 10.0)
        ok += raw is not None
    for i in range(n_expired):
        broker.get_result(f"x{i}", 10.0)
    m = cs.metrics()
    cs.drain(timeout_s=10.0)
    serving_families = sorted(
        f.name for f in REGISTRY.families()
        if f.name.startswith("zoo_serving_"))
    sched = m["scheduler"]
    return _emit("SERVING_PLANE", {
        "policy": sched["policy"],
        "models": sched["models"],
        "records_out": m["records_out"],
        "per_model_records": {k: v["records_out"]
                              for k, v in sched["per_model"].items()},
        "shed_expired": m["resilience"]["shed_expired"],
        "results_ok": ok,
        "metric_families": serving_families})


def snapshot_fleet() -> int:
    """The scale-out serving tier end to end: a two-worker ServingFleet
    (separate processes, shared-nothing) fanning over one FileBroker
    spool as a consumer group — live workers seen through broker
    heartbeats, records served across the fleet, and the idle-reclaim
    counter (zero here: nobody dies in the snapshot; the chaos leg lives
    in bench.py / tests)."""
    import functools

    import numpy as np

    from ..serving.codecs import decode_payload, encode_payload
    from ..serving.fleet import ServingFleet, sleep_model_factory
    from ..serving.queue_api import make_broker

    with tempfile.TemporaryDirectory() as d:
        spec = f"file://{d}/fleet?claim_idle_s=2.0"
        fleet = ServingFleet(
            functools.partial(sleep_model_factory, 2.0, 5.0), spec,
            workers=2, autoscale=False, batch_size=4, max_inflight=8,
            heartbeat_s=0.2, worker_ttl_s=2.0, drain_s=10.0).start()
        broker = make_broker(spec)
        ok = 0
        try:
            live_ok = fleet.wait_live(2, 30.0)
            n = 48
            for i in range(n):
                broker.enqueue(f"s{i}", encode_payload(
                    np.ones(4, np.float32)))
            for i in range(n):
                raw = broker.get_result(f"s{i}", 20.0)
                if raw is not None:
                    out, meta = decode_payload(raw)
                    ok += not meta.get("error")
        finally:
            snap = fleet.stop()
    return _emit("FLEET", {
        "workers": snap["workers_target"],
        "workers_live_ok": bool(live_ok),
        "requests": n, "results_ok": ok,
        "records_out_total": snap["records_out_total"],
        "reclaimed_total": snap["reclaimed_total"],
        "restarts": snap["restarts"]})


def snapshot_shm() -> int:
    """The shared-memory object plane end to end: descriptor frames for a
    handful of serving-codec tensors through a FileBroker spool with
    ``ZOO_SHM=1`` — one slab copy per request, zero-copy consumer
    mappings, inline-fallback accounting, and a clean drain (0 live
    allocations after every ``done``)."""
    import numpy as np

    from .. import shm
    from ..serving.codecs import decode_ref, encode_payload_ref
    from ..serving.queue_api import make_broker

    prev = os.environ.get("ZOO_SHM")
    os.environ["ZOO_SHM"] = "1"
    try:
        with tempfile.TemporaryDirectory() as d:
            spec = f"file://{d}/shm"
            arena = shm.arena_for_spec(spec)
            if arena is None:
                return _emit("SHM", {"enabled": False})
            broker = make_broker(spec)
            rng = np.random.RandomState(0)
            n, descriptor, zero_copy = 8, 0, 0
            try:
                for i in range(n):
                    # 128 KB tensors: comfortably over the ZOO_SHM_MIN_BYTES
                    # floor, so every frame takes the descriptor path
                    x = rng.rand(32768).astype(np.float32)
                    frame, _ = encode_payload_ref(x, arena=arena)
                    descriptor += shm.is_envelope(frame)
                    broker.enqueue(f"s{i}", frame)
                    (rid, raw), = broker.claim_batch(1, 5.0)
                    data, _meta, refs = decode_ref(raw, arena=arena)
                    view = np.asarray(data)
                    zero_copy += (view.base is not None
                                  and not view.flags.writeable)
                    ok = bool(np.array_equal(view, x))
                    del data, view
                    broker.ack(rid)
                    for r in refs:
                        arena.done(r)
                    if not ok:
                        return _emit("SHM", {"error": "roundtrip mismatch"})
                stats = arena.stats()
                swept = arena.sweep()
                return _emit("SHM", {
                    "enabled": True, "requests": n,
                    "descriptor_frames": int(descriptor),
                    "zero_copy_mappings": int(zero_copy),
                    "allocs_live_after_drain": stats["allocs_live"],
                    "segments": stats["segments"],
                    "leases_swept": swept["leases_swept"]})
            finally:
                arena.destroy()
    finally:
        if prev is None:
            os.environ.pop("ZOO_SHM", None)
        else:
            os.environ["ZOO_SHM"] = prev


def snapshot_analysis() -> int:
    """Repo lint findings, golden program-contract drift, and the HLO
    linter's hook report from a fit on the simulated mesh."""
    _ensure_sim_devices()
    import flax.linen as nn
    import numpy as np

    from .. import init_orca_context
    from ..analysis import golden, repolint
    from ..analysis.hlo_lint import lint_report
    from ..orca.learn.estimator import TPUEstimator

    init_orca_context("cpu-sim", mesh_axes={"dp": -1})

    repo_findings = repolint.lint_paths(repolint.repo_roots())
    golden_ok, golden_delta = golden.check()

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    est = TPUEstimator(M(), loss="mse", optimizer="adam", seed=0,
                       config={"steps_per_dispatch": 1})
    est.fit({"x": rng.rand(128, 8).astype(np.float32),
             "y": rng.rand(128).astype(np.float32)},
            epochs=1, batch_size=32, verbose=False)
    hlo = lint_report()
    return _emit("ANALYSIS", {
        "repolint_rules": list(repolint.RULES),
        "repolint_findings": len(repo_findings),
        "golden_drift": len(golden_delta),
        "hlo_programs_linted": hlo["programs_linted"],
        "hlo_findings": hlo["by_rule"]})


def snapshot_obs() -> int:
    """The observability plane's own health line: a traced 8-step fit with
    a checkpoint, then — spans recorded, one trace id across
    fit → engine dispatch → infeed lane → ckpt writer, metric series
    registered, and both exporters round-tripping."""
    from . import trace
    from .export import (parse_exposition, perfetto_trace, prometheus_text)
    from .registry import REGISTRY

    trace.clear()
    trace.arm()
    from .export import _demo_fit
    _demo_fit(8)
    spans = trace.spans()
    by_name: Dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    fit_traces = {s.trace_id for s in by_name.get("fit", ())}
    chained = [n for n in ("engine.dispatch", "infeed.h2d", "ckpt.write")
               if any(s.trace_id in fit_traces
                      for s in by_name.get(n, ()))]
    try:
        prom = parse_exposition(prometheus_text())
        exporter_ok = len(prom) > 0
    except ValueError:
        exporter_ok = False
    doc = perfetto_trace(spans)
    perfetto_ok = bool(doc["traceEvents"]) and all(
        e["ph"] in ("X", "M", "C") for e in doc["traceEvents"])
    return _emit("OBS", {
        "spans": len(spans),
        "span_names": sorted(by_name),
        "one_trace_across": chained,
        "trace_ok": len(chained) == 3,
        "metrics_registered": len(REGISTRY.families()),
        "metric_series": len(REGISTRY.snapshot()),
        "exporter_ok": bool(exporter_ok),
        "perfetto_ok": perfetto_ok})


def snapshot_streaming() -> int:
    """The online-learning loop end to end on the bundled MiniRedisServer:
    producer XADD -> windowed ChunkedArray ingest -> incremental fit ->
    ckpt commit (cursor + trace in the manifest) -> hot-reload into a live
    InferenceModel — records/s, freshness lag, zero recompiles after the
    warm window, and the one-trace-id chain across all four thread hops."""
    import time

    import flax.linen as nn
    import numpy as np

    from .. import init_orca_context
    from ..orca.learn.estimator import TPUEstimator
    from ..pipeline.inference.inference_model import InferenceModel
    from ..serving.queue_api import RedisBroker
    from ..serving.redis_protocol import MiniRedisServer
    from ..streaming import (StreamingReloader, StreamingTrainer,
                             StreamingXShards, encode_record, seq_id)
    from . import trace

    init_orca_context("local")

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    w_true = np.arange(8).astype(np.float32) / 8.0
    srv = MiniRedisServer().start()
    prod = RedisBroker(srv.host, srv.port, stream="t", group="g")
    for i in range(64):
        x = rng.rand(8).astype(np.float32)
        prod.enqueue(seq_id(i), encode_record(
            x, np.float32(x @ w_true), event_time=time.time()))

    est = None
    try:
        with tempfile.TemporaryDirectory() as d:
            est = TPUEstimator(M(), loss="mse", optimizer="adam", seed=0,
                               model_dir=d)
            src = StreamingXShards(
                RedisBroker(srv.host, srv.port, stream="t", group="g"),
                batch_size=16, window_records=32, poll_timeout_s=0.05)
            tr = StreamingTrainer(est, src, d)
            import jax
            model = InferenceModel()
            model.load_jax(M(), {"params": jax.device_get(M().init(
                jax.random.PRNGKey(0),
                np.zeros((1, 8), np.float32))["params"])})
            rel = StreamingReloader(model, d, poll_s=60, start_at=-1,
                                    stats=src.stats)
            trace.clear()
            trace.arm()
            try:
                tr.run(max_windows=2, idle_timeout_s=5.0)
                rel.poll_now()
            finally:
                # stop the async ckpt writer BEFORE TemporaryDirectory
                # cleanup even when the run raised — a live writer racing
                # the rmtree buries the real error in checkpoint noise
                est.shutdown()
                est = None
            by_name: Dict[str, set] = {}
            for s in trace.spans():
                by_name.setdefault(s.name, set()).add(s.trace_id)
            need = ("stream.ingest", "stream.assemble", "engine.dispatch",
                    "ckpt.write", "stream.reload")
            chained = [t for t in by_name.get("stream.window", ())
                       if all(t in by_name.get(n, ()) for n in need)]
            snap = src.stats.snapshot()
    finally:
        if est is not None:
            est.shutdown()
        srv.stop()
    fleet_info = _snapshot_streaming_fleet()
    return _emit("STREAMING", {
        "windows": snap["windows"],
        "records_trained": snap["records_trained"],
        "records_per_s": snap.get("last_records_per_s"),
        "freshness_lag_s": snap.get("last_freshness_lag_s"),
        "reloads": snap["reloads"],
        "recompiles_after_warm": snap["recompiles_after_warm"],
        "trace_ok": len(chained) >= 1,
        "fleet": fleet_info})


def _snapshot_streaming_fleet() -> Dict:
    """The PR-19 scale-out story at snapshot size: a 2-consumer
    StreamingFleet over keyed sub-streams (per-consumer freshness skew —
    worst/best p99 across partitions, ~1.0 when the key hash balances),
    plus one guardrail-reject exercise (a poisoned commit scored on a
    clean holdout must be rejected and never adopted)."""
    import functools
    import shutil
    import time

    import numpy as np

    from ..serving.queue_api import make_broker
    from ..serving.redis_protocol import MiniRedisServer
    from ..streaming import (FleetReloaders, GuardrailEvaluator,
                             StreamingFleet, StreamingReloader,
                             StreamingTrainer, StreamingXShards,
                             encode_record, partition_for, seq_id)
    from ..streaming.fleet import linear_estimator_factory
    from ..streaming.guardrail import module_loss_scorer

    class _Sink:
        def __init__(self):
            self.steps = []

        def apply_checkpoint(self, path, state, step):
            self.steps.append(int(step))

    w_true = (np.arange(8) / 8.0).astype(np.float32)
    srv = MiniRedisServer(port=0).start()
    root = tempfile.mkdtemp(prefix="zoo-snap-fleet-")
    guard_dir = tempfile.mkdtemp(prefix="zoo-snap-guard-")
    fleet = guard_est = None
    try:
        # --- 2-consumer fleet over keyed sub-streams ----------------------
        spec = f"redis://127.0.0.1:{srv.port}/snapf?claim_idle_ms=500"
        prod = make_broker(f"{spec}&partitions=2")
        keys = {0: next(f"k{j}" for j in range(64)
                        if partition_for(f"k{j}", 2) == 0),
                1: next(f"k{j}" for j in range(64)
                        if partition_for(f"k{j}", 2) == 1)}
        rng = np.random.RandomState(1)
        for i in range(64):             # 2 windows of 16 per partition
            x = rng.rand(8).astype(np.float32)
            prod.enqueue(seq_id(i), encode_record(
                x, np.float32([x @ w_true]), event_time=time.time(),
                key=keys[i % 2]))
        fleet = StreamingFleet(
            functools.partial(linear_estimator_factory, dim=8),
            spec, root, consumers=2, batch_size=16, window_records=16,
            poll_timeout_s=0.05, idle_timeout_s=5.0, heartbeat_s=0.2)
        fleet.start()
        m = {}
        if fleet.join(timeout_s=180):
            m = fleet.stop()
        reloaders = FleetReloaders({0: _Sink(), 1: _Sink()}, root,
                                   poll_s=60)
        reloaders.poll_now()
        p99s = [v for v in
                reloaders.freshness_p99_by_consumer().values()
                if v is not None]
        reloaders.stop()
        ratio = (round(max(p99s) / max(min(p99s), 1e-9), 3)
                 if len(p99s) == 2 else None)

        # --- guardrail: poisoned commit rejected, never adopted -----------
        guard_est = linear_estimator_factory(dim=8, lr=0.3)
        gprod = make_broker(f"redis://127.0.0.1:{srv.port}/snapg")
        gsrc = StreamingXShards(
            f"redis://127.0.0.1:{srv.port}/snapg",
            batch_size=16, window_records=32, poll_timeout_s=0.05)
        gtr = StreamingTrainer(guard_est, gsrc, guard_dir)
        guard = GuardrailEvaluator(
            module_loss_scorer(guard_est.module), holdout_records=32,
            min_holdout=16, regression=0.5)
        grng = np.random.RandomState(2)
        for _ in range(32):
            x = grng.rand(8).astype(np.float32)
            guard.observe(x, np.float32([x @ w_true]))
        gsink = _Sink()
        grel = StreamingReloader(gsink, guard_dir, poll_s=60,
                                 start_at=-1, guard=guard)
        gi = [0]

        def g_window(poison):
            for _ in range(32):
                x = grng.rand(8).astype(np.float32)
                y = x @ w_true + (10.0 if poison else 0.0)
                gprod.enqueue(seq_id(gi[0]), encode_record(
                    x, np.float32([y]), event_time=time.time()))
                gi[0] += 1

        g_window(poison=False)
        gtr.run(max_windows=1, idle_timeout_s=5.0)
        grel.poll_now()                 # clean commit: accepted + adopted
        g_window(poison=True)
        gtr.run(max_windows=1, idle_timeout_s=5.0)
        poisoned_step = int(guard_est.engine.step)
        grel.poll_now()                 # poisoned commit: rejected
        gsnap = grel.stats.snapshot()
        return {
            "consumers": int(m.get("consumers", 2)),
            "windows_total": int(m.get("windows_total", 0)),
            "freshness_p99_ratio": ratio,
            "guard_rejected": int(gsnap.get("guard_rejected", 0)),
            "guard_accepted": int(gsnap.get("guard_accepted", 0)),
            "rejected_never_adopted": bool(
                poisoned_step not in gsink.steps),
        }
    finally:
        if fleet is not None:
            fleet.stop()
        if guard_est is not None:
            guard_est.shutdown()
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)
        shutil.rmtree(guard_dir, ignore_errors=True)


PLANES = {"transfer": snapshot_transfer, "ckpt": snapshot_ckpt,
          "sharding": snapshot_sharding,
          "resilience": snapshot_resilience,
          "serving": snapshot_serving, "fleet": snapshot_fleet,
          "streaming": snapshot_streaming, "shm": snapshot_shm,
          "analysis": snapshot_analysis, "obs": snapshot_obs}


def run(plane: str) -> int:
    fn = PLANES.get(plane)
    if fn is None:
        print(f"unknown plane {plane!r}; choose from {sorted(PLANES)}",
              file=sys.stderr)
        return 2
    return fn()


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if len(args) != 1:
        print("usage: python -m analytics_zoo_tpu.obs.snapshots <plane>",
              file=sys.stderr)
        return 2
    return run(args[0])


if __name__ == "__main__":
    raise SystemExit(main())
