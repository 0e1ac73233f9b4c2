#!/usr/bin/env python
"""Benchmark: ResNet-50 ImageNet + NCF-MovieLens training throughput on TPU.

Primary metric (the BASELINE.md north star): ResNet-50 ImageNet training
samples/sec/chip measured END-TO-END — synthetic uint8 image shards on disk,
memory-mapped host crop/flip assembly, batches fed through the input pipeline
into the jitted train step every measured step (reference workload config:
pyzoo/zoo/examples/orca/learn/tf2/resnet/resnet-50-imagenet.py:26-33,351).

Also reported (extras in the same JSON line + BENCH_DETAIL.json):
  - compute-only samples/sec/chip (device-resident batches) and MFU from the
    XLA-compiled step's own cost analysis vs the chip's peak bf16 rate;
  - the measured host->device transfer rate with live training state.

Where it runs: the six device legs (resnet50, ncf, fraud_mlp, autots,
serving_od, attention) measure the chip and REFUSE any platform but ``tpu``
— there is no CPU fallback; a machine without the chip fails. Every other leg
is a count/contract check that CI runs with an explicit ``JAX_PLATFORMS=cpu``.
Every result names ``platform``/``device_kind``/``device_count``; a leg that
raises makes the exit code non-zero. A chip belongs to one process: the parent
stays off JAX until a leg needs the device, the legs whose work happens in
child processes run first, and those children get an explicit
``JAX_PLATFORMS=cpu``. ``chip_smoke.py`` is the quick proof that the main
path runs on the chip at all.

Measurement notes (to be retired by ROADMAP S0): timed sections end with a
value fetch (``float(loss)``) and the headline e2e loop feeds the jit from the
main thread instead of going through ``TPUEstimator.fit``. On the v5e host
``block_until_ready`` does wait and ``fit``'s background ``device_put`` pump
works (chip_smoke.py's sync_probe / train stage, CHANGES.md PR 21), so both
habits are history rather than necessity.

Baselines: the reference publishes no absolute numbers (BASELINE.md); target
is >=0.8x Horovod-on-8xA100 per-chip throughput. Constants:
  - ResNet-50: MLPerf-era A100 ~2900 img/s/GPU -> 2900.0 samples/sec/chip.
  - NCF: ~60M samples/sec on 8xV100, ~2x for A100 -> 15M samples/sec/chip.

Prints ONE JSON line {"metric","value","unit","vs_baseline", ...extras} and
writes per-workload detail to BENCH_DETAIL.json.
"""

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

RESNET_BASELINE = 2900.0        # A100 img/s, see module docstring
NCF_BASELINE = 15_000_000.0

# the peak-bf16 table lives with the production fuse heuristic so there is
# exactly one copy to maintain
from analytics_zoo_tpu.orca.learn.utils import (ASSUMED_TRAIN_MFU,
                                                peak_bf16_flops as
                                                _peak_flops)


# what the host-only legs give their child processes: a chip belongs to one
# process, and a child that came up on the TPU (or failed to) unannounced
# would make a CPU contract check measure something else
_CHILD_ENV = {"JAX_PLATFORMS": "cpu"}


def _compile_totals() -> dict:
    """Cumulative compile-plane counters (empty when the plane is off)."""
    from analytics_zoo_tpu.compile import compile_stats
    snap = compile_stats()
    snap.pop("by_label", None)
    return snap


def _compile_delta(before: dict, after: dict) -> dict:
    """Per-workload compile attribution: counters accrued by one bench."""
    return {k: round(after.get(k, 0) - before.get(k, 0), 6)
            for k in set(before) | set(after)}


def _step_flops(jitted, args, fallback: float) -> float:
    """FLOPs of one compiled step from XLA's own cost analysis (``fallback``
    only where the backend reports none; a failing compile raises)."""
    cost = jitted.lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    f = float((cost or {}).get("flops", 0.0))
    return f if f > 0 else fallback


def _mesh_peak_flops():
    """Summed peak bf16 FLOP/s of every device, None on the CPU (no MFU
    there); an unknown TPU kind raises (orca.learn.utils.PEAK_BF16_FLOPS)."""
    import jax
    peaks = [_peak_flops(d) for d in jax.devices()]
    return None if None in peaks else sum(peaks)


def _device_label() -> dict:
    """What every result says about where it ran."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": jax.device_count()}


def _require_tpu(leg: str):
    """Device legs measure the chip; a number from any other platform would
    be written under the name of a device metric."""
    label = _device_label()
    if label["platform"] != "tpu":
        raise RuntimeError(
            f"bench leg {leg!r} measures the device and refuses platform "
            f"{label['platform']!r} ({label['device_kind']}); run it on a "
            "TPU host")


def _param_count(params) -> int:
    import jax
    return sum(int(np.prod(l.shape))
               for l in jax.tree_util.tree_leaves(params))


def _hot_mbps(arr) -> float:
    """Host->device rate with live state on the queue. Warms the transfer
    path first and times a >=8MB probe best-of-2, so the number is
    bandwidth- not dispatch-latency-dominated."""
    import jax
    a = np.asarray(arr)
    if a.nbytes < 8 << 20:
        reps = (8 << 20) // max(a.nbytes, 1) + 1
        a = np.concatenate([a] * reps)
    jax.device_put(a).block_until_ready()          # warm
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        jax.device_put(a).block_until_ready()
        best = max(best, a.nbytes / (time.perf_counter() - t0) / 1e6)
    return best


def _compute_loop(engine, dev_batches, steps: int,
                  compute_s=None) -> float:
    """Steady-state seconds/step through the PRODUCTION dispatch loop on
    device-resident batches — i.e. exactly what ``fit()`` does: time one
    dispatched step, let ``auto_fuse_factor`` pick the scan-fusion k, then
    drive ``train_batch_group`` (k>1) or ``train_batch`` (k==1) per
    dispatch. A fetch at the end forces the chain (see module docstring)."""
    from analytics_zoo_tpu.orca.learn.utils import Batch, auto_fuse_factor

    loss = engine.train_batch(dev_batches[0])   # warm/compile
    float(loss)
    m = min(8, steps)
    dt1 = float("inf")
    for _ in range(2):              # min-of-2 washes out contention spikes
        t0 = time.perf_counter()
        for i in range(m):
            loss = engine.train_batch(dev_batches[i % len(dev_batches)])
        float(loss)
        dt1 = min(dt1, (time.perf_counter() - t0) / m)
    batch_bytes = sum(int(getattr(a, "nbytes", 0))
                      for a in tuple(dev_batches[0].x)
                      + tuple(dev_batches[0].y or ()))
    k = auto_fuse_factor(dt1, max(steps, 256), batch_bytes=batch_bytes,
                         compute_s=compute_s)
    if k <= 1:
        t0 = time.perf_counter()
        n = 0
        while n < steps:
            for b in dev_batches:
                loss = engine.train_batch(b)
                n += 1
                if n >= steps:
                    break
        float(loss)
        return (time.perf_counter() - t0) / steps
    import jax.numpy as jnp
    groups = []
    for start in range(0, max(len(dev_batches) - k + 1, 1), k):
        picks = [dev_batches[(start + i) % len(dev_batches)]
                 for i in range(k)]
        groups.append(Batch(
            x=tuple(jnp.stack([b.x[j] for b in picks])
                    for j in range(len(picks[0].x))),
            y=(tuple(jnp.stack([b.y[j] for b in picks])
                     for j in range(len(picks[0].y)))
               if picks[0].y is not None else None),
            w=None, fused=k))
    float(engine.train_batch_group(groups[0])[-1])   # warm/compile
    ndisp = max(steps // k, 4)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        n = 0
        while n < ndisp:
            for g in groups:
                loss = engine.train_batch_group(g)
                n += 1
                if n >= ndisp:
                    break
        float(loss[-1])
        best = min(best, (time.perf_counter() - t0) / (ndisp * k))
    return best


def _compute_loop_scanned(engine, dev_batch, steps: int) -> float:
    """Pure chip rate: `steps` train steps inside ONE jitted lax.scan, so
    per-step host dispatch is excluded; for small models (NCF/MLP) the
    per-dispatch loop above measures dispatch, not the chip."""
    import jax
    import jax.numpy as jnp

    step_fn = engine._train_step
    x, y, w = dev_batch.x, dev_batch.y, dev_batch.w

    @jax.jit
    def multi(params, extra, opt_state):
        def body(carry, i):
            params, extra, opt_state = carry
            params, extra, opt_state, loss = step_fn(
                params, extra, opt_state, i, x, y, w)
            return (params, extra, opt_state), loss
        (params, extra, opt_state), losses = jax.lax.scan(
            body, (params, extra, opt_state), jnp.arange(steps))
        return params, extra, opt_state, losses[-1]

    p, e, o = engine.params, engine.extra_vars, engine.opt_state
    p, e, o, l = multi(p, e, o)
    float(l)                                    # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        p, e, o, l = multi(p, e, o)
        float(l)
        best = min(best, (time.perf_counter() - t0) / steps)
    engine.params, engine.extra_vars, engine.opt_state = p, e, o
    return best


def bench_streaming(smoke: bool) -> dict:
    """Streaming-plane bench: the online-learning loop end to end on the
    bundled MiniRedisServer — a producer thread XADDs NCF-style records
    while the StreamingTrainer consumes count windows through incremental
    fit and commits through the checkpoint plane, and a hot-reload
    watcher swaps each commit into a live InferenceModel.

    Reported: trained records/s (the headline ``value``), per-reload
    freshness lag (event time of the newest trained record -> wall clock
    at adoption) p50/p99, reload count, and the zero-recompile assertion
    — after window 1's single compile, every later window and every
    reload must reuse the warm executables (``recompiles_after_warm == 0``
    and 0 serving compiles across reloads), compile_stats-asserted.
    CPU-friendly; tier1.yml gates zero_recompile + reloads >= 1.
    """
    import tempfile
    import threading

    import flax.linen as nn
    import jax

    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel
    from analytics_zoo_tpu.serving.queue_api import RedisBroker
    from analytics_zoo_tpu.serving.redis_protocol import MiniRedisServer
    from analytics_zoo_tpu.streaming import (StreamingReloader,
                                             StreamingTrainer,
                                             StreamingXShards,
                                             encode_record, seq_id)
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator

    n_users, n_items = (600, 370) if smoke else (6040, 3706)
    embed = 8 if smoke else 32
    batch = 64 if smoke else 256
    window = batch * 2 if smoke else batch * 4
    n_windows = 3 if smoke else 8
    total = window * n_windows

    class OnlineNCF(nn.Module):
        """Two-tower dot-product NCF (the streaming guide's demo model)."""
        @nn.compact
        def __call__(self, pairs):
            import jax.numpy as jnp
            u = nn.Embed(n_users, embed)(pairs[:, 0])
            v = nn.Embed(n_items, embed)(pairs[:, 1])
            x = jnp.concatenate([u * v, u, v], axis=-1)
            x = nn.relu(nn.Dense(embed)(x))
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    srv = MiniRedisServer().start()
    prod = RedisBroker(srv.host, srv.port, stream="ncf", group="train")

    stop_feed = threading.Event()

    def feed():
        for i in range(total):
            if stop_feed.is_set():
                return
            pair = np.array([rng.randint(0, n_users),
                             rng.randint(0, n_items)], np.int32)
            rating = np.float32(rng.rand())
            prod.enqueue(seq_id(i), encode_record(
                pair, rating, event_time=time.time()))

    feeder = threading.Thread(target=feed, name="stream-producer",
                              daemon=True)

    root = tempfile.mkdtemp(prefix="zoo-stream-bench-")
    est = reloader = None
    try:
        module = OnlineNCF()
        est = TPUEstimator(module, loss="mse", optimizer="adam", seed=0,
                           model_dir=root)
        src = StreamingXShards(
            RedisBroker(srv.host, srv.port, stream="ncf", group="train"),
            batch_size=batch, window_records=window, poll_timeout_s=0.05)
        trainer = StreamingTrainer(est, src, root)

        model = InferenceModel()
        model.load_jax(module, {"params": jax.device_get(module.init(
            jax.random.PRNGKey(0),
            np.zeros((1, 2), np.int32))["params"])})
        probe = np.stack([np.arange(8) % n_users,
                          np.arange(8) % n_items], -1).astype(np.int32)
        model.predict(probe)            # warm the serving bucket

        def serving_compiles_now() -> int:
            # the model compiles through the PROCESS-WIDE cache; count only
            # its own "serving"-labelled programs, not the trainer's
            if model._cc is None:
                return 0
            return int(model._cc.stats.counts("serving")["compiles"])

        serving_compiles_before = serving_compiles_now()
        reloader = StreamingReloader(model, root, poll_s=0.05,
                                     start_at=-1, stats=src.stats).start()

        feeder.start()
        t0 = time.perf_counter()
        trainer.run(max_windows=n_windows, idle_timeout_s=30.0)
        wall = time.perf_counter() - t0
        # let the watcher adopt the final commit before reading counters
        deadline = time.time() + 5.0
        while reloader.stats.snapshot().get("last_reload_step") != \
                est.engine.step and time.time() < deadline:
            time.sleep(0.05)
        model.predict(probe)            # post-reload predict: warm path
        serving_compiles = serving_compiles_now() - serving_compiles_before
        snap = src.stats.snapshot()
        p50, p99 = reloader.freshness_percentiles()
        records_per_s = snap["records_trained"] / max(wall, 1e-9)
        zero_recompile = (snap["recompiles_after_warm"] == 0
                          and serving_compiles == 0)
        return {
            "metric": "streaming_records_per_sec",
            "value": round(records_per_s, 1),
            "unit": "records/s",
            # freshness is the plane's SLO; a single-host CPU loop that
            # keeps lag within one window of wall time is "at baseline"
            "vs_baseline": (round(min(1.0, (wall / n_windows) / p99), 3)
                            if p99 else None),
            "windows": snap["windows"],
            "records_trained": snap["records_trained"],
            "freshness_p50_s": round(p50, 3) if p50 is not None else None,
            "freshness_p99_s": round(p99, 3) if p99 is not None else None,
            "reloads": snap["reloads"],
            "recompiles_after_warm": snap["recompiles_after_warm"],
            "serving_reload_compiles": serving_compiles,
            "zero_recompile": bool(zero_recompile),
            "backlog_final": snap.get("last_backlog"),
        }
    finally:
        # stop the watcher + ckpt writer BEFORE deleting their root, on
        # the failure path too — a live writer racing the rmtree buries
        # the real error under unreadable-checkpoint noise
        stop_feed.set()
        if reloader is not None:
            reloader.stop()
        if est is not None:
            est.shutdown()
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)


def bench_streaming_fleet(smoke: bool) -> dict:
    """Fleet-scale streaming bench — three legs over the real Redis
    transport (bundled MiniRedisServer), asserting the scale-out story
    end to end:

    1. **freshness linearity** — the same aggregate record rate through 1
       consumer and through 4 (keyed sub-streams, per-consumer window =
       aggregate window / N): worst-consumer freshness p99 going 1 -> 4
       must stay within 1.3x of the single-consumer p99 (the headline
       ``value``; per-consumer windows shrink with N, so window fill time
       — the freshness floor — is flat by design).
    2. **guardrail reject** — a poisoned window's commit is scored on a
       clean holdout, rejected, and NEVER adopted (no ``stream.reload``
       span for that step, ``guard.reject`` chained under the commit's
       trace), while a later clean commit is adopted on its own merits.
    3. **SIGKILL replay** — one of two consumers is SIGKILLed
       mid-stream; the supervisor respawns it onto its partition, the
       PEL replays its unacked claims, and the partition's final
       committed weights are byte-identical to an uninterrupted
       reference run while the surviving consumer keeps progressing.
    """
    import functools
    import tempfile
    import threading

    import jax

    from analytics_zoo_tpu.ckpt import format as ckpt_fmt
    from analytics_zoo_tpu.obs import trace as _trace
    from analytics_zoo_tpu.serving.queue_api import make_broker
    from analytics_zoo_tpu.serving.redis_protocol import MiniRedisServer
    from analytics_zoo_tpu.streaming import (FleetReloaders,
                                             GuardrailEvaluator,
                                             StreamingFleet,
                                             StreamingReloader,
                                             StreamingTrainer,
                                             StreamingXShards,
                                             encode_record, partition_for,
                                             seq_id)
    from analytics_zoo_tpu.streaming.fleet import linear_estimator_factory
    from analytics_zoo_tpu.streaming.guardrail import module_loss_scorer

    BS, DIM = 16, 8
    W_TRUE = (np.arange(DIM) / DIM).astype(np.float32)

    class _Sink:
        """Serving-model stand-in: records adopted steps."""
        def __init__(self):
            self.steps = []

        def apply_checkpoint(self, path, state, step):
            self.steps.append(int(step))

    def _keys_by_partition(n, per):
        """``per`` distinct keys per partition, so a round-robin producer
        feeds every partition the same record count while still routing
        through the real key hash."""
        out = [[] for _ in range(n)]
        j = 0
        while any(len(o) < per for o in out):
            k = f"user-{j}"
            p = partition_for(k, n)
            if len(out[p]) < per:
                out[p].append(k)
            j += 1
        return out

    # --- leg 1: freshness linearity at fixed aggregate rate ---------------
    agg_window = 4 * BS                       # whole-fleet records per window
    n_windows = 8 if smoke else 12            # per consumer
    rate = 256.0 if smoke else 512.0          # aggregate records/s

    def _freshness_run(n_consumers):
        srv = MiniRedisServer(port=0).start()
        root = tempfile.mkdtemp(prefix="zoo-fleetb-")
        spec = f"redis://127.0.0.1:{srv.port}/fleetb?claim_idle_ms=500"
        fleet = reloaders = None
        stop_feed = threading.Event()
        try:
            fleet = StreamingFleet(
                functools.partial(linear_estimator_factory, dim=DIM),
                spec, root, consumers=n_consumers, batch_size=BS,
                window_records=agg_window // n_consumers,
                poll_timeout_s=0.05, idle_timeout_s=20.0, heartbeat_s=0.2,
                worker_env=_CHILD_ENV)
            reloaders = FleetReloaders(
                {k: _Sink() for k in range(n_consumers)}, root,
                poll_s=0.02).start()
            prod = make_broker(f"{spec}&partitions={n_consumers}")
            keys = _keys_by_partition(n_consumers, 16)
            total = agg_window * n_windows
            rng = np.random.default_rng(7)

            def emit(i, paced_from=None):
                p = i % n_consumers
                x = rng.normal(size=DIM).astype(np.float32)
                y = np.float32([x @ W_TRUE])
                prod.enqueue(seq_id(i), encode_record(
                    x, y, event_time=time.time(),
                    key=keys[p][(i // n_consumers) % len(keys[p])]))

            def feed():
                period = 1.0 / rate
                t_next = time.perf_counter()
                for i in range(agg_window, agg_window + total):
                    if stop_feed.is_set():
                        return
                    emit(i)
                    t_next += period
                    dt = t_next - time.perf_counter()
                    if dt > 0:
                        time.sleep(dt)

            fleet.start()
            if not fleet.wait_live(timeout_s=90):
                raise RuntimeError("fleet consumers never went live")
            # warm-up: one un-paced aggregate window pays every
            # consumer's single window-1 compile BEFORE the measured
            # feed — the 1.3x linearity bound is about steady state,
            # not about N cold JITs racing each other for cores
            for i in range(agg_window):
                emit(i)
            deadline = time.time() + 120.0
            while time.time() < deadline and any(
                    not r.freshness_samples
                    for r in reloaders.reloaders.values()):
                time.sleep(0.05)
            warm = {k: len(r.freshness_samples)
                    for k, r in reloaders.reloaders.items()}
            feeder = threading.Thread(target=feed, name="fleet-producer",
                                      daemon=True)
            feeder.start()
            if not fleet.join(timeout_s=240):
                raise RuntimeError("fleet consumers never drained")
            feeder.join(timeout=10)
            m = fleet.stop()
            # let the reloaders adopt the final commits
            deadline = time.time() + 5.0
            while time.time() < deadline and reloaders.poll_now():
                time.sleep(0.02)
            # worst-consumer p99 over the post-warm-up samples only
            p99s = []
            for k, r in reloaders.reloaders.items():
                s = r.freshness_samples[warm[k]:] or r.freshness_samples
                if s:
                    p99s.append(float(np.percentile(s, 99)))
            if not p99s:
                raise RuntimeError("no freshness samples collected")
            return max(p99s), m
        finally:
            stop_feed.set()
            if reloaders is not None:
                reloaders.stop()
            if fleet is not None:
                fleet.stop()
            srv.stop()
            shutil.rmtree(root, ignore_errors=True)

    p99_1c, m_1c = _freshness_run(1)
    p99_4c, m_4c = _freshness_run(4)
    ratio = p99_4c / max(p99_1c, 1e-9)

    # --- leg 2: guardrail reject (in-parent, span-asserted) ----------------
    def _guard_leg():
        srv = MiniRedisServer(port=0).start()
        root = tempfile.mkdtemp(prefix="zoo-fleetg-")
        est = None
        try:
            est = linear_estimator_factory(dim=DIM, lr=0.3)
            prod = make_broker(f"redis://127.0.0.1:{srv.port}/guardb")
            src = StreamingXShards(
                f"redis://127.0.0.1:{srv.port}/guardb",
                batch_size=BS, window_records=4 * BS, poll_timeout_s=0.05)
            trainer = StreamingTrainer(est, src, root)
            guard = GuardrailEvaluator(
                module_loss_scorer(est.module), holdout_records=64,
                min_holdout=32, regression=0.5, baseline_window=8)
            rng = np.random.default_rng(11)
            for _ in range(64):     # clean holdout the scorer judges on
                x = rng.normal(size=DIM).astype(np.float32)
                guard.observe(x, np.float32([x @ W_TRUE]))
            sink = _Sink()
            reloader = StreamingReloader(sink, root, poll_s=0.05,
                                         start_at=-1, guard=guard)
            seq = [0]

            def feed_window(poison):
                for _ in range(4 * BS):
                    x = rng.normal(size=DIM).astype(np.float32)
                    y = x @ W_TRUE + (10.0 if poison else 0.0)
                    prod.enqueue(seq_id(seq[0]), encode_record(
                        x, np.float32([y]), event_time=time.time()))
                    seq[0] += 1

            with _trace.tracing():
                feed_window(poison=False)
                trainer.run(max_windows=1, idle_timeout_s=10.0)
                if not reloader.poll_now():
                    raise RuntimeError("clean window was not adopted")
                feed_window(poison=True)
                trainer.run(max_windows=1, idle_timeout_s=10.0)
                rejected_step = int(est.engine.step)
                adopted_poison = reloader.poll_now()
                # reject-then-later-accept: clean windows repair the
                # weights; a LATER commit must adopt on its own merits
                readopted = None
                for _ in range(6):
                    feed_window(poison=False)
                    trainer.run(max_windows=1, idle_timeout_s=10.0)
                    if reloader.poll_now():
                        readopted = int(est.engine.step)
                        break
                spans = _trace.spans()
            snap = reloader.stats.snapshot()
            reject_spans = [s for s in spans if s.name == "guard.reject"]
            reload_steps = [s.attrs.get("step") for s in spans
                            if s.name == "stream.reload"]
            return {
                "rejected_step": rejected_step,
                "rejected": int(snap.get("guard_rejected", 0)),
                "accepted": int(snap.get("guard_accepted", 0)),
                "readopted_step": readopted,
                # the acceptance bar: the rejected commit is NEVER adopted
                "rejected_never_adopted": bool(
                    not adopted_poison
                    and rejected_step not in sink.steps
                    and rejected_step not in reload_steps),
                "span_ok": bool(
                    any(s.attrs.get("step") == rejected_step
                        for s in reject_spans)
                    and readopted is not None
                    and readopted in reload_steps),
            }
        finally:
            if est is not None:
                est.shutdown()
            srv.stop()
            shutil.rmtree(root, ignore_errors=True)

    guard_res = _guard_leg()

    # --- leg 3: SIGKILL one consumer, PEL replay, bit-exact weights --------
    chaos_windows = 4 if smoke else 8

    def _chaos_run(kill):
        srv = MiniRedisServer(port=0).start()
        root = tempfile.mkdtemp(prefix="zoo-fleetc-")
        spec = f"redis://127.0.0.1:{srv.port}/fleetc?claim_idle_ms=300"
        fleet = None
        try:
            keys = _keys_by_partition(2, 4)
            prod = make_broker(f"{spec}&partitions=2")
            # the whole feed lands up front with FIXED event times: ref
            # and chaos runs must consume byte-identical streams
            i = 0
            rng = np.random.default_rng(23)
            for w in range(chaos_windows):
                for p in (0, 1):
                    for j in range(BS):
                        x = rng.normal(size=DIM).astype(np.float32)
                        y = np.float32([x @ W_TRUE])
                        prod.enqueue(seq_id(i), encode_record(
                            x, y, event_time=1.0e9 + i * 1e-3,
                            key=keys[p][j % len(keys[p])]))
                        i += 1
            fleet = StreamingFleet(
                functools.partial(linear_estimator_factory, dim=DIM),
                spec, root, consumers=2, batch_size=BS, window_records=BS,
                poll_timeout_s=0.05, idle_timeout_s=6.0, heartbeat_s=0.2,
                worker_env=_CHILD_ENV)
            fleet.start()
            if kill:
                # SIGKILL t0 right after its first commit lands: claimed-
                # but-unacked records sit in partition 0's PEL and must
                # replay through the respawned consumer
                deadline = time.time() + 120
                while time.time() < deadline and not \
                        ckpt_fmt.loadable_step_dirs(fleet.partition_root(0)):
                    time.sleep(0.01)
                if not fleet.kill_consumer(0):
                    raise RuntimeError("kill_consumer(0) found no live "
                                       "consumer")
            if not fleet.join(timeout_s=240):
                raise RuntimeError("fleet consumers never drained")
            m = fleet.stop()
            final = {}
            for p in (0, 1):
                dirs = ckpt_fmt.loadable_step_dirs(fleet.partition_root(p))
                step, path = dirs[-1]
                state = ckpt_fmt.load_checkpoint_dir(path)
                final[p] = (step, state["params"])
            return m, final
        finally:
            if fleet is not None:
                fleet.stop()
            srv.stop()
            shutil.rmtree(root, ignore_errors=True)

    m_ref, final_ref = _chaos_run(kill=False)
    m_chaos, final_chaos = _chaos_run(kill=True)

    def _tree_identical(a, b):
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        return len(la) == len(lb) and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(la, lb))

    bit_identical = (final_ref[0][0] == final_chaos[0][0]
                     and _tree_identical(final_ref[0][1], final_chaos[0][1]))
    survivor_ok = (final_ref[1][0] == final_chaos[1][0]
                   and _tree_identical(final_ref[1][1], final_chaos[1][1]))

    return {
        "metric": "fleet_freshness_p99_ratio",
        "children_platform": _CHILD_ENV["JAX_PLATFORMS"],
        "value": round(ratio, 3),
        "unit": "x (worst-consumer p99, 4 consumers vs 1, fixed "
                "aggregate rate)",
        "vs_baseline": round(min(1.0, 1.3 / max(ratio, 1e-9)), 3),
        "scale": {
            "consumers": 4,
            "freshness_p99_1c_s": round(p99_1c, 3),
            "freshness_p99_4c_s": round(p99_4c, 3),
            "ratio": round(ratio, 3),
            "windows_1c": m_1c["windows_total"],
            "windows_4c": m_4c["windows_total"],
            "restarts": m_1c["restarts"] + m_4c["restarts"],
        },
        "guard": guard_res,
        "chaos": {
            "restarts": m_chaos["restarts"],
            "reclaimed": m_chaos["reclaimed_total"],
            "bit_identical": bool(bit_identical),
            "survivor_ok": bool(survivor_ok),
            "windows_ref": m_ref["windows_total"],
            "windows_chaos": m_chaos["windows_total"],
        },
    }


def _shm_chaos_child(root, ref_dicts):
    """Attach the arena, pin every blob, die without unwinding — the
    SIGKILLed-consumer leg of bench_shm (module-level: spawn pickles it)."""
    import signal as _signal

    from analytics_zoo_tpu import shm as _shm
    a = _shm.BlobArena(root, create=False)
    for d in ref_dicts:
        a.checkout(_shm.ObjectRef.from_dict(d))
    os.kill(os.getpid(), _signal.SIGKILL)


def bench_shm(smoke: bool) -> dict:
    """Shared-memory object plane bench — three legs on the file
    transport (the FLEET snapshot's broker, real spool I/O on disk):

    1. **copied bytes + hop latency** — the same ~128/256 KB request
       tensors pushed through the serving codec inline (today's wire:
       JSON+base64, the inflated payload materialized, spooled, read
       back, then b64-decoded) and as slab descriptors (``ZOO_SHM=1``:
       one copy into the arena, a ~300 B frame through the spool,
       consumer maps the slab read-only). Headline ``value`` is the
       ratio of host bytes copied per request, inline / shm — the gate
       wants >= 2x. Decoded arrays must be BIT-IDENTICAL between legs.
    2. **SIGKILL chaos** — a consumer process pins live blobs and dies
       un-unwound; the supervisor-style sweep drops its lease and the
       drain consumes every blob: 0 leaked segments.
    3. **fsync batching** — N single enqueues vs one ``publish_many``
       on the durable spool (each payload still fsynced; the dir fsync
       amortizes N -> 1).
    """
    import multiprocessing as mp
    import signal
    import tempfile

    from analytics_zoo_tpu import shm
    from analytics_zoo_tpu.serving.codecs import (decode_payload,
                                                  decode_ref,
                                                  encode_payload,
                                                  encode_payload_ref)
    from analytics_zoo_tpu.serving.queue_api import make_broker

    n_msgs = 16 if smoke else 64
    elems = 32_768 if smoke else 65_536     # f32 -> 128 KB / 256 KB
    rng = np.random.RandomState(7)
    tensors = [rng.rand(elems).astype(np.float32) for _ in range(n_msgs)]

    root = tempfile.mkdtemp(prefix="zoo-shm-bench-")
    prev_shm = os.environ.get("ZOO_SHM")
    os.environ["ZOO_SHM"] = "1"
    try:
        # --- leg 1a: inline serving wire (ZOO_SHM=0: JSON+b64 payloads).
        # Host bytes copied per request: the encoded payload is
        # materialized by the producer, written to the spool, read back by
        # the consumer (3x its inflated ~1.33N size), then base64-decode
        # materializes the N tensor bytes once more.
        b_in = make_broker(f"file://{root}/inline")
        lat_in, copied_in, decoded_in = [], 0, []
        for i, x in enumerate(tensors):
            t0 = time.perf_counter()
            p = encode_payload(x)
            b_in.enqueue(f"r{i}", p)
            (rid, raw), = b_in.claim_batch(1, 5.0)
            data, _meta = decode_payload(raw)
            decoded_in.append(np.asarray(data))
            lat_in.append(time.perf_counter() - t0)
            b_in.ack(rid)
            copied_in += 3 * len(p) + decoded_in[-1].nbytes
        # --- leg 1b: descriptor wire, SAME tensors (ZOO_SHM=1). One copy
        # into the slab; the ~300 B frame rides the spool; the consumer
        # maps the slab read-only — zero further tensor-byte copies.
        spec = f"file://{root}/shm"
        arena = shm.arena_for_spec(spec)
        if arena is None:
            raise RuntimeError("shm unavailable on this host")
        b_ref = make_broker(spec)
        lat_shm, copied_shm, decoded_shm = [], 0, []
        for i, x in enumerate(tensors):
            t0 = time.perf_counter()
            frame, _prefs = encode_payload_ref(x, arena=arena)
            b_ref.enqueue(f"r{i}", frame)
            (rid, raw), = b_ref.claim_batch(1, 5.0)
            data, _meta, refs = decode_ref(raw, arena=arena)
            view = np.asarray(data)
            bit_ok = np.array_equal(view, decoded_in[i])
            decoded_shm.append(bit_ok)
            lat_shm.append(time.perf_counter() - t0)
            b_ref.ack(rid)
            del data, view          # slab views must die before done/destroy
            for r in refs:
                arena.done(r)
            copied_shm += x.nbytes + 3 * len(frame)
        bit_identical = all(decoded_shm)
        shm_leftover = arena.stats()["allocs_live"]
        copy_ratio = copied_in / max(copied_shm, 1)

        # --- leg 2: SIGKILL chaos sweep ---
        blob = tensors[0].tobytes()
        refs = []
        for i in range(8):
            r = arena.put(blob)
            arena.release(r)
            refs.append(r)
        child = mp.get_context("spawn").Process(
            target=_shm_chaos_child,
            args=(arena.root, [r.to_dict() for r in refs]))
        # a spawned child inherits os.environ as it is at start()
        prev = os.environ.get("JAX_PLATFORMS")
        os.environ.update(_CHILD_ENV)
        try:
            child.start()
        finally:
            if prev is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = prev
        child.join(60)
        # the child pins BEFORE it SIGKILLs itself, so by the time join
        # returns its lease file (with live pins) is on disk
        chaos_killed = child.exitcode == -signal.SIGKILL
        swept = arena.sweep([child.pid])
        for r in refs:              # drain: the replayed deliveries consume
            arena.done(r)
        leaked = int(arena.stats()["allocs_live"])

        # --- leg 3: fsync batching (count syscalls, not wall time — on
        # hosts where the journal commit is cheap the timing is pure
        # noise, but the N-dir-fsyncs -> 1 collapse is deterministic) ---
        from analytics_zoo_tpu.serving import queue_api as _qa
        fb = _qa.FileBroker(f"{root}/fsync")
        real_fsync, counts = os.fsync, [0]

        def _counting_fsync(fd):
            counts[0] += 1
            return real_fsync(fd)

        _qa.os.fsync = _counting_fsync
        try:
            t0 = time.perf_counter()
            for k in range(n_msgs):
                fb.enqueue(f"s{k}", blob)
            t_single = time.perf_counter() - t0
            fsyncs_single = counts[0]
            counts[0] = 0
            t0 = time.perf_counter()
            fb.publish_many([(f"m{k}", blob) for k in range(n_msgs)])
            t_batch = time.perf_counter() - t0
            fsyncs_batch = counts[0]
        finally:
            _qa.os.fsync = real_fsync

        arena.destroy()
        return {
            "metric": "shm_copied_bytes_ratio",
            "value": round(copy_ratio, 2),
            "unit": "x_inline_over_shm",
            "vs_baseline": None,
            "copied_bytes_per_req_inline": copied_in // n_msgs,
            "copied_bytes_per_req_shm": copied_shm // n_msgs,
            "hop_p50_ms_inline": round(
                sorted(lat_in)[len(lat_in) // 2] * 1e3, 3),
            "hop_p50_ms_shm": round(
                sorted(lat_shm)[len(lat_shm) // 2] * 1e3, 3),
            "bit_identical": bool(bit_identical),
            "hotpath_leftover_allocs": int(shm_leftover),
            "chaos": {
                "killed": bool(chaos_killed),
                "leases_swept": int(swept["leases_swept"]),
                "leaked_allocs_after_sweep": leaked,
            },
            "fsync": {
                "n_items": n_msgs,
                "fsyncs_enqueue_loop": fsyncs_single,
                "fsyncs_publish_many": fsyncs_batch,
                "enqueue_n_s": round(t_single, 4),
                "publish_many_s": round(t_batch, 4),
            },
        }
    finally:
        if prev_shm is None:
            os.environ.pop("ZOO_SHM", None)
        else:
            os.environ["ZOO_SHM"] = prev_shm
        shutil.rmtree(root, ignore_errors=True)


def bench_resnet50(smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.common.context import get_context
    from analytics_zoo_tpu.models.image.resnet import resnet
    from analytics_zoo_tpu.orca.data.image import (ImageNetPipeline,
                                                   write_synthetic_imagenet)
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.optimizers import SGD
    from analytics_zoo_tpu.orca.learn.optimizers.schedule import (
        Poly, SequentialSchedule, Warmup)

    ctx = get_context()
    if smoke:
        batch, num_images, image_size, crop, steps, depth = \
            64, 256, 72, 64, 6, 18
    else:
        batch, num_images, image_size, crop, steps, depth = \
            256, 2048, 232, 224, 30, 50

    data_dir = tempfile.mkdtemp(prefix="zoo_bench_imagenet_")
    try:
        write_synthetic_imagenet(data_dir, num_images=num_images,
                                 image_size=image_size, shard_size=1024)
        pipe = ImageNetPipeline(data_dir, batch_size=batch, mesh=ctx.mesh,
                                crop_size=crop, train=True)
        # reference LR recipe: peak 0.1*global/256, 5-epoch warmup, poly decay
        peak = 0.1 * pipe.global_bs / 256
        warm = 5 * pipe.steps_per_epoch
        sched = (SequentialSchedule()
                 .add(Warmup(delta=peak / warm), warm)
                 .add(Poly(2.0, 85 * pipe.steps_per_epoch),
                      85 * pipe.steps_per_epoch))
        est = TPUEstimator(
            resnet(depth=depth, num_classes=1000),
            loss="sparse_categorical_crossentropy",
            optimizer=SGD(learningrate=0.0, momentum=0.9,
                          leaningrate_schedule=sched))

        sample = next(pipe.epoch(shuffle=False, prefetch=False))
        est.engine.build(tuple(np.asarray(a) for a in sample.x))
        hb = list(pipe._host_batches(True))
        # compile + warm (value fetch forces completion)
        float(est.engine.train_batch(hb[0]))
        float(est.engine.train_batch(hb[1 % len(hb)]))

        flops_fallback = 3 * 4.09e9 * (crop / 224) ** 2 * batch
        step_flops = _step_flops(
            est.engine._jit_train,
            (est.engine.params, est.engine.extra_vars, est.engine.opt_state,
             0, tuple(np.asarray(a) for a in hb[0].x),
             tuple(np.asarray(a) for a in hb[0].y), hb[0].w),
            flops_fallback)

        # 1) compute-only: device-resident batches, fetch once at the end
        dev = [pipe._put_batch(b) for b in hb[:4]]
        float(est.engine.train_batch(dev[0]))
        t0 = time.perf_counter()
        n = 0
        while n < steps:
            for b in dev:
                loss = est.engine.train_batch(b)
                n += 1
                if n >= steps:
                    break
        float(loss)
        dt_compute = (time.perf_counter() - t0) / steps

        # 2) transfer probe with live training state (the e2e constraint)
        probe = np.random.randint(0, 255, hb[0].x[0].shape, np.uint8)
        t0 = time.perf_counter()
        jax.device_put(probe).block_until_ready()
        hot_mbps = probe.nbytes / (time.perf_counter() - t0) / 1e6

        # 2b) demonstrated-ceiling probe: best sustained bf16 matmul rate on
        # THIS device right now (8192^3, chained in-jit). The nominal spec
        # peak is not attainable on shared/fractional dev chips, so MFU is
        # reported against both (docs/performance_notes.md round-3 notes).
        @jax.jit
        def _mm_chain(a):
            return jax.lax.fori_loop(0, 8, lambda i, acc: acc @ a, a)
        mm = jax.device_put(jnp.ones((8192, 8192), jnp.bfloat16))
        float(_mm_chain(mm)[0, 0].astype(jnp.float32))
        best_probe = 0.0
        for _ in range(3):      # best-of-3: shared-chip contention is spiky
            t0 = time.perf_counter()
            out = _mm_chain(mm)
            float(out[0, 0].astype(jnp.float32))
            best_probe = max(best_probe,
                             2 * 8192**3 * 8 / (time.perf_counter() - t0))
        # the probe runs on one device; scale to the whole mesh so the
        # step-FLOPs numerator (all chips) divides a like-for-like ceiling
        achievable = best_probe * max(jax.device_count(), 1)

        # 3) end-to-end: every step assembles a fresh host batch from the
        #    memory-mapped shards and feeds it straight into the jit
        t0 = time.perf_counter()
        n = 0
        while n < steps:
            for b in pipe._host_batches(True):
                loss = est.engine.train_batch(b)
                n += 1
                if n >= steps:
                    break
        float(loss)
        dt_e2e = (time.perf_counter() - t0) / steps

        # 4) production pumped path, a short pass: per-stage MB/s and the
        #    transfer_limited verdict measured on the real prefetch+lanes
        #    pipeline (data_pipeline_stats is the surface perf PRs read)
        pipe.stats.reset()
        est._pipeline_stats = pipe.stats
        est.engine.pipeline_stats = pipe.stats
        pumped = 0
        for b in pipe.epoch(shuffle=True):
            loss = est.engine.train_batch(b)
            pumped += 1
            if pumped >= min(steps, 8):
                break
        float(loss)
        pipe_stats = pipe.stats.snapshot()

        # wire format: bytes/sample the uint8 wire ships vs the f32 host-
        # side-normalize path it replaces (narrow-dtype tentpole; labels
        # ride int32 either way)
        wire_bps = sum(int(a.nbytes) for a in hb[0].x + hb[0].y) / batch
        f32_bps = sum(int(a.size) * 4 for a in hb[0].x + hb[0].y) / batch

        nchip = max(jax.device_count(), 1)
        peak_rate = _mesh_peak_flops()
        e2e = batch / dt_e2e / nchip
        comp = batch / dt_compute / nchip
        # flag runs where the streamed numbers measure a slow host->HBM
        # link, not the framework (compute_* fields carry the chip signal)
        transfer_limited = bool(hot_mbps < 200.0)
        return {"metric": "resnet50_imagenet_train_throughput_per_chip",
                "value": round(e2e, 1), "unit": "samples/sec/chip",
                "vs_baseline": round(e2e / RESNET_BASELINE, 3),
                "compute_samples_per_sec_per_chip": round(comp, 1),
                "compute_vs_baseline": round(comp / RESNET_BASELINE, 3),
                "mfu_compute": (round(step_flops / dt_compute / peak_rate, 4)
                                if peak_rate else None),
                "mfu_vs_achievable": round(
                    step_flops / dt_compute / achievable, 4),
                "achievable_tflops_probe": round(achievable / 1e12, 1),
                "mfu_e2e": (round(step_flops / dt_e2e / peak_rate, 4)
                            if peak_rate else None),
                "hot_transfer_MBps": round(hot_mbps, 1),
                "transfer_limited": transfer_limited,
                "wire_bytes_per_sample": round(wire_bps, 1),
                "f32_bytes_per_sample": round(f32_bps, 1),
                "wire_reduction_x": round(f32_bps / wire_bps, 2),
                "data_pipeline_stats": pipe_stats,
                "batch": batch, "depth": depth, "crop": crop,
                "streamed": True, "step_flops": step_flops}
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def bench_ncf(smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.common.context import get_context
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    from analytics_zoo_tpu.orca.learn.optimizers import Adam
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    ctx = get_context()
    n_users, n_items = 6040, 3706
    # 256k/chip: NCF is fixed-overhead-bound below ~64k (scripts/ncf_probe.py
    # round 4: the step costs ~2ms whether or not the embeddings exist);
    # MLPerf-class NCF runs use comparable global batches (~1M over 8 GPUs)
    batch = 2048 if smoke else 262144
    steps = 10 if smoke else 30

    rng = np.random.RandomState(0)
    n = batch * 8
    pairs = np.stack([rng.randint(1, n_users, n),
                      rng.randint(1, n_items, n)], -1).astype(np.int32)
    ratings = rng.randint(0, 5, n).astype(np.int32)

    model = NeuralCF(user_count=n_users, item_count=n_items, class_num=5,
                     user_embed=64, item_embed=64, hidden_layers=(128, 64, 32),
                     mf_embed=64, compute_dtype=jnp.bfloat16)
    model.compile(loss="sparse_categorical_crossentropy",
                  optimizer=Adam(lr=1e-3), metrics=None)
    est = model.estimator

    it = data_to_iterator({"x": pairs, "y": ratings}, batch, ctx.mesh,
                          shuffle=True)
    est.engine.build((pairs[:1],))
    hb = []
    for b in it._host_batches(True):
        hb.append(b)
        if len(hb) >= 4:
            break
    float(est.engine.train_batch(hb[0]))
    float(est.engine.train_batch(hb[0]))

    step_flops = _step_flops(
        est.engine._jit_train,
        (est.engine.params, est.engine.extra_vars, est.engine.opt_state,
         0, tuple(np.asarray(a) for a in hb[0].x),
         tuple(np.asarray(a) for a in hb[0].y), hb[0].w),
        6.0 * _param_count(est.engine.params) * batch)

    # 1) compute-only: device-resident batches — per-dispatch loop AND a
    #    scanned (dispatch-free) run; the scanned one is the chip rate
    dev = [it._put_batch(b) for b in hb]
    peak_pre = _mesh_peak_flops()
    dt_compute = _compute_loop(
        est.engine, dev, steps,
        compute_s=(step_flops / (ASSUMED_TRAIN_MFU * peak_pre)
                   if peak_pre else None))
    dt_scanned = _compute_loop_scanned(est.engine, dev[0],
                                       max(steps, 50))

    hot_mbps = _hot_mbps(hb[0].x[0])

    # 2) e2e: shuffle + native gather + feed, every step (fetch forces finish)
    t0 = time.perf_counter()
    done = 0
    while done < steps:
        for b in it._host_batches(True):
            loss = est.engine.train_batch(b)
            done += 1
            if done >= steps:
                break
    float(loss)
    dt = (time.perf_counter() - t0) / steps

    # 3) production input path: one fit() through the chunked assembler +
    #    pipelined infeed so the per-stage data-plane timers are measured on
    #    the real NCF config (data_pipeline_stats is the observability
    #    surface every perf PR reads first)
    pipe_stats = {}
    if hasattr(est, "data_pipeline_stats"):
        est.data_pipeline_stats(reset=True)
        est.fit({"x": pairs, "y": ratings}, epochs=1, batch_size=batch,
                verbose=False)
        pipe_stats = est.data_pipeline_stats()
        print("ncf data_pipeline_stats:", json.dumps(pipe_stats))

    nchip = max(jax.device_count(), 1)
    peak_rate = _mesh_peak_flops()
    per_chip = batch / dt / nchip
    comp = batch / dt_scanned / nchip
    return {"metric": "ncf_movielens_train_throughput_per_chip",
            "data_pipeline_stats": pipe_stats,
            "value": round(per_chip, 1), "unit": "samples/sec/chip",
            "vs_baseline": round(per_chip / NCF_BASELINE, 3),
            "compute_samples_per_sec_per_chip": round(comp, 1),
            "compute_vs_baseline": round(comp / NCF_BASELINE, 3),
            "compute_dispatch_loop_per_chip": round(
                batch / dt_compute / nchip, 1),
            "mfu_compute": (round(step_flops / dt_scanned / peak_rate, 4)
                            if peak_rate else None),
            "hot_transfer_MBps": round(hot_mbps, 1),
            "transfer_limited": bool(hot_mbps < 200.0),
            "batch": batch, "streamed": True}


def bench_fraud_mlp(smoke: bool) -> dict:
    """BASELINE config #3: NNEstimator fraud-detection MLP (reference runs a
    Keras-style MLP over NNEstimator/NNFrames on a Spark cluster; here the
    NNFrames path feeds the jitted engine). Tabular binary classification on
    synthetic card-fraud-shaped data (29 features, heavy class imbalance)."""
    import jax
    import pandas as pd
    from analytics_zoo_tpu.pipeline.nnframes import NNEstimator

    n_features = 29
    batch = 1024 if smoke else 16384
    n = batch * 4
    epochs = 1 if smoke else 3
    rng = np.random.RandomState(0)
    x = rng.rand(n, n_features).astype(np.float32)
    y = (rng.rand(n) < 0.02).astype(np.float32)   # ~2% fraud
    df = pd.DataFrame({"features": list(x), "label": y})

    import flax.linen as nn

    class FraudMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            for width in (256, 128, 64):
                x = nn.relu(nn.Dense(width)(x))
            return nn.sigmoid(nn.Dense(1)(x))[..., 0]

    from analytics_zoo_tpu.common.context import get_context
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    est = (NNEstimator(FraudMLP(), "binary_crossentropy")
           .setBatchSize(batch).setMaxEpoch(epochs))
    # warm fit compiles the step; re-running fit on the SAME underlying
    # engine (NNModel keeps it) measures steady-state epochs with the
    # jit hot — no retrace, no recompile in the timed window
    model = est.fit(df)
    inner = model.estimator
    x_all = np.stack(df["features"].to_numpy())
    # y shape must match the warm fit's (n,1) (NNEstimator reshapes
    # labels) or the jit retraces inside the timed window
    y_all = df["label"].to_numpy(np.float32).reshape(-1, 1)

    step_flops = _step_flops(
        inner.engine._jit_train,
        (inner.engine.params, inner.engine.extra_vars,
         inner.engine.opt_state, 0, (x_all[:batch],), (y_all[:batch],), None),
        6.0 * _param_count(inner.engine.params) * batch)

    # 1) compute-only: device-resident batches
    it = data_to_iterator({"x": x_all, "y": y_all}, batch, get_context().mesh,
                          shuffle=True)
    hb = []
    for b in it._host_batches(True):
        hb.append(b)
        if len(hb) >= 4:
            break
    dev = [it._put_batch(b) for b in hb]
    peak_pre = _mesh_peak_flops()
    dt_compute = _compute_loop(
        inner.engine, dev, 12 if smoke else 40,
        compute_s=(step_flops / (ASSUMED_TRAIN_MFU * peak_pre)
                   if peak_pre else None))
    dt_scanned = _compute_loop_scanned(inner.engine, dev[0],
                                       50 if smoke else 100)

    hot_mbps = _hot_mbps(hb[0].x[0])

    # 2) streamed: full fit epochs through the NNFrames feed path
    t0 = time.perf_counter()
    inner.fit({"x": x_all, "y": y_all},
              epochs=epochs, batch_size=batch, verbose=False)
    dt = time.perf_counter() - t0
    samples = n * epochs
    nchip = max(jax.device_count(), 1)
    peak_rate = _mesh_peak_flops()
    per_chip = samples / dt / nchip
    comp = batch / dt_scanned / nchip
    # no published reference number; estimate: this 4-layer MLP on one A100
    # sustains ~8M samples/s (batch-bound) -> scaled constant like NCF's
    base = 8_000_000.0
    return {"metric": "nnestimator_fraud_mlp_throughput_per_chip",
            "value": round(per_chip, 1), "unit": "samples/sec/chip",
            "vs_baseline": round(per_chip / base, 3),
            "compute_samples_per_sec_per_chip": round(comp, 1),
            "compute_vs_baseline": round(comp / base, 3),
            "compute_dispatch_loop_per_chip": round(
                batch / dt_compute / nchip, 1),
            "mfu_compute": (round(step_flops / dt_scanned / peak_rate, 4)
                            if peak_rate else None),
            "hot_transfer_MBps": round(hot_mbps, 1),
            "transfer_limited": bool(hot_mbps < 200.0),
            "batch": batch, "epochs": epochs, "streamed": True}


def bench_autots_trials(smoke: bool) -> dict:
    """BASELINE config #4: Zouwu AutoTS hyperparameter trials. The reference
    farms LSTM/TCN trials to Ray workers; here trials run chip-pinned through
    TPUSearchEngine. Metric: completed trials/hour (per chip)."""
    import pandas as pd
    from analytics_zoo_tpu.zouwu.autots.forecast import AutoTSTrainer
    from analytics_zoo_tpu.zouwu.config.recipe import (LSTMGridRandomRecipe,
                                                       TCNGridRandomRecipe)

    n_points = 400 if smoke else 2000
    ts = pd.date_range("2024-01-01", periods=n_points, freq="h")
    rng = np.random.RandomState(0)
    value = (np.sin(np.arange(n_points) / 24 * 2 * np.pi) +
             0.1 * rng.randn(n_points)).astype(np.float32)
    df = pd.DataFrame({"datetime": ts, "value": value})

    # MIXED search (round-4 verdict: an LSTM-only space was statistically
    # thin): each timed round runs an LSTM grid-random search AND a TCN
    # grid-random search — the two model families the reference's AutoTS
    # notebooks actually tune together
    n_trials = 1 if smoke else 2
    recipes = [LSTMGridRandomRecipe(num_rand_samples=n_trials,
                                    epochs=1 if smoke else 5),
               TCNGridRandomRecipe(num_rand_samples=n_trials,
                                   training_iteration=1 if smoke else 5)]
    trainer = AutoTSTrainer(dt_col="datetime", target_col="value", horizon=1)
    # contention discipline: first full round is warmup (XLA compiles per
    # trial shape; the engine's fixed seed makes repeat fits sample
    # identical configs), then repeated timed rounds on the hot cache —
    # best-of-N headline plus per-round spread. Smoke skips the warmup.
    if not smoke:
        for recipe in recipes:
            assert trainer.fit(df, validation_df=None,
                               recipe=recipe) is not None
    rounds = 1 if smoke else 3
    round_times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for recipe in recipes:
            assert trainer.fit(df, validation_df=None,
                               recipe=recipe) is not None
        round_times.append(time.perf_counter() - t0)
    best_dt = min(round_times)
    # trial count mirrors TPUSearchEngine.compile: grid axes × num_samples
    from analytics_zoo_tpu.automl import hp as hp_dsl
    trials_done = sum(
        len(hp_dsl.grid_configs(r.search_space([]))) * r.num_samples
        for r in recipes)
    per_hour = trials_done / best_dt * 3600.0
    # reference point: the AutoTS use-case notebook budgets ~30 LSTM trials
    # per hour per worker on Xeon (no published number; estimate)
    base = 30.0
    return {"metric": "autots_mixed_trials_per_hour",
            "value": round(per_hour, 1), "unit": "trials/hour/chip",
            "vs_baseline": round(per_hour / base, 3),
            "trials": trials_done, "series_len": n_points,
            "recipes": ["LSTMGridRandom", "TCNGridRandom"],
            "timed_rounds": rounds,
            "round_s": [round(t, 2) for t in round_times],
            "round_s_mean": round(float(np.mean(round_times)), 2),
            "round_s_std": round(float(np.std(round_times)), 2),
            "best_round_s": round(best_dt, 2)}


def _run_serving_load(serving, broker, imgs, n_req):
    """Drive n_req requests through a running ClusterServing; returns
    (records/sec, steady-state stage summary). Warmup batches run first and
    the timers are reset, so percentiles exclude any residual one-time cost."""
    from analytics_zoo_tpu.serving import InputQueue, OutputQueue

    iq = InputQueue(queue=broker, max_pending=256)
    oq = OutputQueue(queue=broker)
    for i in range(32):
        iq.enqueue(f"warm-{i}", t=imgs[i % len(imgs)])
    oq.dequeue([f"warm-{i}" for i in range(32)], timeout_s=300)
    serving.reset_metrics()

    t0 = time.perf_counter()
    uris = []
    for i in range(n_req):
        uris.append(iq.enqueue(f"r-{i}", t=imgs[i % len(imgs)]))
    results = oq.dequeue(uris, timeout_s=300)
    dt = time.perf_counter() - t0
    assert len(results) == n_req
    bad = [u for u, v in results.items() if np.asarray(v).shape != (20, 6)]
    assert not bad, (f"{len(bad)} serving results are error payloads "
                     f"(first: {bad[0]})")
    return n_req / dt, serving.metrics()["stages"]


def bench_serving_od(smoke: bool) -> dict:
    """BASELINE config #5: Cluster-Serving object detection. Tiny-SSD served
    through the batching engine over (a) the in-memory broker — engine+model
    number, matching how the reference reads Flink numRecordsOutPerSecond —
    and (b) the bundled MiniRedisServer via the RESP2 RedisBroker, the
    transport users actually deploy. All shape buckets are precompiled by
    ``start(example=...)`` so percentiles are steady-state. Also reports the
    compute-side records/sec of the jitted detector on device-resident
    batches (the chip-capability signal, independent of host transfer)."""
    import jax
    from analytics_zoo_tpu.models.image.objectdetection import ObjectDetector
    from analytics_zoo_tpu.serving import (ClusterServing, InMemoryBroker,
                                           MiniRedisServer, RedisBroker)

    size = 64 if smoke else 128
    n_req = 64 if smoke else 512
    # bucket sized to the model: tiny-SSD convs at batch 16 leave the chip
    # idle between launches; 64 quadruples per-dispatch parallelism and is
    # still a 12 MB batch (r5)
    batch = 16 if smoke else 64
    det = ObjectDetector(class_names=("a", "b", "c"), image_size=size,
                         model_type="ssd_tiny", max_gt=4)
    det.compile()
    # serve in bf16 (the detector's default on TPU): serving ingress sends
    # f32 images, which would otherwise run the conv trunk at f32 rate
    model = det.as_inference_model(max_detections=20)
    rng = np.random.RandomState(0)
    imgs = rng.rand(n_req, size, size, 3).astype(np.float32)

    # compute-side: chained inside one jit (per-dispatch platform overhead
    # is ms-scale here — docs/performance_notes.md round-5 notes), input
    # perturbed by the previous iteration's output so iterations serialize
    import jax.numpy as jnp
    repeat = 4 if smoke else 8

    @jax.jit
    def apply_chain(variables, x):
        def body(i, carry):
            x2, acc = carry
            out = model._apply_fn(variables, x2)
            bump = jax.tree_util.tree_leaves(out)[0].astype(
                jnp.float32).sum() * 1e-20
            return (x + bump, acc + bump)
        return jax.lax.fori_loop(
            0, repeat, body, (x, jnp.zeros((), jnp.float32)))[1]

    dev_in = jax.device_put(imgs[:batch])
    float(apply_chain(model._variables, dev_in))   # compile
    best = float("inf")
    pipeline = 3
    for _ in range(3 if smoke else 5):
        t0 = time.perf_counter()
        for _ in range(pipeline):
            o = apply_chain(model._variables, dev_in)
        float(o)
        best = min(best, (time.perf_counter() - t0))
    dt_compute = best / (repeat * pipeline)
    comp = batch / dt_compute
    jit_apply = jax.jit(model._apply_fn)
    step_flops = _step_flops(jit_apply, (model._variables, imgs[:batch]), 0.0)
    peak_rate = _mesh_peak_flops()

    # conv-trunk probe (same chained discipline, no decode/NMS): the
    # roofline for this model is NOT the dense-matmul peak — tiny-SSD
    # convs carry <=64 channels, so the 128x128 MXU runs half-empty by
    # shape, on top of XLA's conv-emitter efficiency (perf notes round 2:
    # representative convs reach 6-9% of nominal even dispatch-free).
    # trunk_ms vs full_ms also shows what decode/NMS adds.
    ssd_mod, eng = det.module, det.estimator.engine
    trunk_vars = {"params": eng.params, **eng.extra_vars}

    @jax.jit
    def trunk_chain(v, x):
        def body(i, carry):
            x2, acc = carry
            loc, _ = ssd_mod.apply(v, x2.astype(jnp.bfloat16))
            bump = loc.astype(jnp.float32).sum() * 1e-20
            return (x + bump, acc + bump)
        return jax.lax.fori_loop(
            0, repeat, body, (x, jnp.zeros((), jnp.float32)))[1]

    float(trunk_chain(trunk_vars, dev_in))
    tbest = float("inf")
    for _ in range(3 if smoke else 5):
        t0 = time.perf_counter()
        for _ in range(pipeline):
            o = trunk_chain(trunk_vars, dev_in)
        float(o)
        tbest = min(tbest, (time.perf_counter() - t0))
    dt_trunk = tbest / (repeat * pipeline)

    broker = InMemoryBroker()
    serving = ClusterServing(model, queue=broker, batch_size=batch,
                             batch_timeout_ms=5).start(example=imgs[:1])
    try:
        per_sec, stages = _run_serving_load(serving, broker, imgs, n_req)
    finally:
        serving.stop()
    infer = stages.get("inference", {})

    # (b) through MiniRedisServer + RESP2 RedisBroker — the shipped transport
    redis_res = {}
    srv = MiniRedisServer(port=0).start()
    try:
        rbroker = RedisBroker("127.0.0.1", srv.port,
                              stream=f"bench-od-{os.getpid()}")
        # same InferenceModel instance, so buckets are already hot — pass the
        # example anyway so this path stays precompiled under BENCH_ONLY
        serving2 = ClusterServing(model, queue=rbroker, batch_size=batch,
                                  batch_timeout_ms=5).start(example=imgs[:1])
        try:
            n_redis = max(n_req // 2, 32)
            rps, rstages = _run_serving_load(serving2, rbroker, imgs, n_redis)
            rinfer = rstages.get("inference", {})
            # NOTE: no in-memory-vs-redis "overhead" derived metric — the
            # difference has only been seen inside run-to-run noise
            redis_res = {
                "redis_records_per_sec": round(rps, 1),
                "redis_inference_ms_mean": round(rinfer.get("mean_ms", 0.0), 2),
                "redis_requests": n_redis}
        finally:
            serving2.stop()
    finally:
        srv.stop()

    # HEADLINE is the compute-side rate; e2e records/sec and the stage
    # latencies ride along (e2e_transfer_limited flags a slow host->HBM
    # link, as in bench_resnet50). The 200 rec/s
    # denominator is an unpublished CPU-serving ESTIMATE — the reference
    # publishes no absolute serving number (BASELINE.md:16) and only
    # points at Flink's numRecordsOutPerSecond as the method.
    hot_mbps = _hot_mbps(imgs[:batch])
    res = {"metric": "cluster_serving_od_compute_throughput",
           "value": round(comp, 1), "unit": "records/sec/chip",
           "vs_baseline": round(comp / 200.0, 3),
           "baseline_note": "200 rec/s CPU-serving estimate; reference "
                            "publishes no absolute number",
           "mfu_compute": (round(step_flops / dt_compute / peak_rate, 4)
                           if peak_rate and step_flops else None),
           "trunk_records_per_sec": round(batch / dt_trunk, 1),
           "decode_nms_ms_per_batch": round(
               (dt_compute - dt_trunk) * 1e3, 2),
           "serve_dtype": "bfloat16",
           "roofline_note": ("tiny-SSD convs carry <=64 channels so the "
                             "128-wide MXU runs half-empty by shape; the "
                             "conv trunk alone is the model's floor — see "
                             "docs/performance_notes.md round-5"),
           "e2e_records_per_sec": round(per_sec, 1),
           "e2e_transfer_limited": bool(hot_mbps < 200.0),
           "hot_transfer_MBps": round(hot_mbps, 1),
           "image_size": size, "requests": n_req,
           "inference_ms_mean": round(infer.get("mean_ms", 0.0), 2),
           "inference_ms_p50": round(infer.get("p50_ms", 0.0), 2),
           "inference_ms_p95": round(infer.get("p95_ms", 0.0), 2),
           "inference_ms_p99": round(infer.get("p99_ms", 0.0), 2)}
    res.update(redis_res)
    return res


def _serving_scale_leg(broker, inputs, rate_rps, n_req, deadline_s, rng,
                       n_fetchers=8):
    """One open-loop leg: Poisson arrivals at ``rate_rps`` across the
    models in ``inputs`` (name -> one record), absolute deadlines stamped
    at enqueue. Latency is accounted at the engine's completion stamp
    (result meta ``t_done``), independent of fetcher scheduling. Returns
    ok/shed/error counts + admitted-latency percentiles."""
    import queue as _queue
    import threading

    from analytics_zoo_tpu.serving.codecs import decode_payload, \
        encode_payload

    names = sorted(inputs)
    results = {}
    lock = threading.Lock()
    uri_q: "_queue.Queue" = _queue.Queue()
    _STOP = object()

    def fetch_loop():
        while True:
            item = uri_q.get()
            if item is _STOP:
                return
            uri, t_enq, dl = item
            raw = broker.get_result(uri, max(dl - time.time(), 0.0) + 5.0)
            t_ret = time.time()
            if raw is None:
                rec = ("lost", None)
            else:
                _, meta = decode_payload(raw)
                if meta.get("shed"):
                    rec = ("shed", None)
                elif meta.get("error"):
                    rec = ("error", None)
                else:
                    rec = ("ok", float(meta.get("t_done", t_ret)) - t_enq)
            with lock:
                results[uri] = rec

    fetchers = [threading.Thread(target=fetch_loop, daemon=True,
                                 name=f"serving-scale-fetch-{i}")
                for i in range(n_fetchers)]
    for t in fetchers:
        t.start()
    gaps = rng.exponential(1.0 / rate_rps, n_req)
    t0 = time.time()
    next_t = t0
    for i in range(n_req):
        next_t += gaps[i]
        now = time.time()
        if next_t > now:
            time.sleep(next_t - now)
        name = names[i % len(names)]
        t_enq = time.time()
        dl = t_enq + deadline_s
        uri = f"sl{rate_rps:.0f}-{i}"
        broker.enqueue(uri, encode_payload(
            inputs[name], meta={"uri": uri, "model": name, "deadline": dl}))
        uri_q.put((uri, t_enq, dl))
    enq_wall = time.time() - t0
    for _ in fetchers:
        uri_q.put(_STOP)
    for t in fetchers:
        t.join(timeout=120)
    wall = time.time() - t0
    counts = {"ok": 0, "shed": 0, "error": 0, "lost": 0}
    lats = []
    for kind, lat in results.values():
        counts[kind] += 1
        if lat is not None:
            lats.append(lat)
    lat_arr = np.asarray(lats) if lats else np.zeros(1)
    return {"offered_rps": round(n_req / max(enq_wall, 1e-9), 1),
            "target_rps": round(rate_rps, 1),
            "requests": n_req,
            "ok": counts["ok"], "shed": counts["shed"],
            "errors": counts["error"] + counts["lost"],
            "lost": counts["lost"],
            "shed_rate": round(counts["shed"] / max(n_req, 1), 4),
            "goodput_rps": round(counts["ok"] / max(wall, 1e-9), 1),
            "p50_ms": round(float(np.percentile(lat_arr, 50) * 1e3), 2),
            "p99_ms": round(float(np.percentile(lat_arr, 99) * 1e3), 2),
            "wall_s": round(wall, 3)}


def bench_serving_scale(smoke: bool) -> dict:
    """ROADMAP open item 4: continuous batching + multi-model multiplexing
    under open-loop overload. Two MLPs co-served on one chip set through
    the deadline-aware EDF batch former; a Poisson load generator offers
    1x/3x/10x of measured capacity with absolute deadlines. Reported:
    p50/p99 of ADMITTED requests (shed requests are the overload valve —
    under 10x the p99 must stay bounded, not collapse), shed rate, chip
    occupancy (busy-seconds delta / wall), and the continuous-vs-fixed A/B
    on the same model at 1x (the acceptance gate: continuous >= fixed).
    Cross-model compile churn is asserted at zero via the compile plane."""
    import flax.linen as nn
    import jax

    from analytics_zoo_tpu.obs import trace as _trace
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.serving import (ClusterServing, InMemoryBroker,
                                           InputQueue, ModelMultiplexer,
                                           OutputQueue)

    dim = 256 if smoke else 512
    width = 1024 if smoke else 2048
    batch = 16 if smoke else 32
    deadline_s = 0.5 if smoke else 0.75

    def make_model(width, n_out, seed):
        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = nn.relu(nn.Dense(width)(x))
                h = nn.relu(nn.Dense(width)(h))
                return nn.Dense(n_out)(h)

        m = Net()
        v = m.init(jax.random.PRNGKey(seed),
                   np.zeros((1, dim), np.float32))
        return InferenceModel().load_jax(m, v)

    rng = np.random.RandomState(7)
    inputs = {"ncf": rng.rand(dim).astype(np.float32),
              "fraud": rng.rand(dim).astype(np.float32)}
    mux = (ModelMultiplexer()
           .add_model("ncf", make_model(width, 8, 0),
                      example=np.zeros((1, dim), np.float32))
           .add_model("fraud", make_model(width // 2, 2, 1),
                      example=np.zeros((1, dim), np.float32)))
    broker = InMemoryBroker()
    serving = ClusterServing(mux, queue=broker, batch_size=batch,
                             slack_ms=25.0, max_inflight=4 * batch).start()
    try:
        # closed-loop capacity rounds: one ~0.2s round is inside ambient
        # CPU noise on this host (measured round spread ~1.7x), and
        # whichever engine runs LATER in the process measures faster
        # (allocator/JIT warmth) — so the A/B below interleaves rounds
        # and takes best-of-N per engine.
        n_probe = 192 if smoke else 512

        def _capacity_round(b, tag):
            iqp, oqp = InputQueue(queue=b), OutputQueue(queue=b)
            t0 = time.perf_counter()
            us = [iqp.enqueue(f"{tag}-{i}", model_name="ncf",
                              t=inputs["ncf"]) for i in range(n_probe)]
            got = oqp.dequeue(us, timeout_s=300)
            rate = n_probe / (time.perf_counter() - t0)
            assert len(got) == n_probe
            return rate

        _capacity_round(broker, "cw")       # warm the continuous path
        capacity = max(_capacity_round(broker, f"c{r}") for r in range(3))
        serving.reset_metrics()
        # cap the base rate to what the encode+enqueue loop sustains at
        # 10x — above it the generator itself becomes closed-loop and the
        # "offered load" label would lie
        base = min(capacity, 300.0 if smoke else 600.0)

        legs = {}
        busy0 = serving.metrics()["scheduler"]["busy_s"]
        compile0 = _compile_totals()
        with _trace.tracing(capacity=8192):
            for mult in (1, 3, 10):
                rate = base * mult
                dur = (1.0 if smoke else 2.0) if mult == 1 else \
                    (0.75 if smoke else 1.5)
                n_req = max(int(rate * dur), 2 * batch)
                b0 = serving.metrics()["scheduler"]["busy_s"]
                w0 = time.time()
                # per-leg seed: the fixed-policy A/B below replays the 1x
                # leg's EXACT arrival stream (seed 101)
                leg = _serving_scale_leg(broker, inputs, rate, n_req,
                                         deadline_s,
                                         np.random.RandomState(100 + mult))
                leg["occupancy"] = round(
                    (serving.metrics()["scheduler"]["busy_s"] - b0)
                    / max(time.time() - w0, 1e-9), 4)
                legs[f"{mult}x"] = leg
            batch_spans = sum(s.name == "serving.batch"
                              for s in _trace.spans())
        sched = serving.metrics()["scheduler"]
        busy_total = sched["busy_s"] - busy0
        per_model = {k: v["records_out"]
                     for k, v in sched["per_model"].items()}
        # cross-model churn receipt: every (model, bucket) executable was
        # warmed at start(); the whole multiplexed run must add ZERO
        # compiles (the zero-compile model-switch claim, PR 3 + PR 6)
        churn = _compile_delta(compile0, _compile_totals())

        # fixed-policy A/B on the same models: (a) the same 1x open-loop
        # stream (arrival-bound: any working engine completes it — the
        # latency columns carry the signal there), and (b) closed-loop
        # saturated rounds INTERLEAVED between the two live engines
        # (back-to-back, not one-then-the-other, per the warmth bias
        # above), best-of-N each
        broker_f = InMemoryBroker()
        fixed = ClusterServing(mux, queue=broker_f, batch_size=batch,
                               batch_timeout_ms=5.0,
                               policy="fixed").start()
        try:
            leg_fixed = _serving_scale_leg(
                broker_f, inputs, base, legs["1x"]["requests"],
                deadline_s, np.random.RandomState(101))
            _capacity_round(broker_f, "fw")     # warm the fixed path
            cont_cap = fixed_capacity = 0.0
            for r in range(4):
                fixed_capacity = max(fixed_capacity,
                                     _capacity_round(broker_f, f"fx{r}"))
                cont_cap = max(cont_cap,
                               _capacity_round(broker, f"cx{r}"))
            capacity = max(capacity, cont_cap)
        finally:
            fixed.stop()
    finally:
        serving.stop()

    # the acceptance gate is the OPEN-LOOP comparison (1x offered load,
    # same models, same Poisson stream): both formers must complete the
    # offered stream, so >= 1.0-within-noise is the pass and the latency
    # columns differentiate. The closed-loop saturated ratio is reported
    # too: there the continuous path pays a few percent of pump-thread
    # GIL contention for its deadline machinery (measured 0.90-0.97x on
    # this host), which open-loop service — the production regime — never
    # sees.
    ratio = (legs["1x"]["goodput_rps"]
             / max(leg_fixed["goodput_rps"], 1e-9))
    return {"metric": "serving_scale_continuous_vs_fixed",
            "value": round(ratio, 3), "unit": "x goodput at 1x open loop",
            "vs_baseline": round(ratio, 3),
            "closed_loop": {
                "continuous_rps": round(cont_cap, 1),
                "fixed_rps": round(fixed_capacity, 1),
                "ratio": round(cont_cap / max(fixed_capacity, 1e-9), 3)},
            "baseline_note": "baseline = the legacy fixed "
                             "batch_size/batch_timeout_ms former on the "
                             "same models and stream",
            "capacity_rps": round(capacity, 1),
            "base_rate_rps": round(base, 1),
            "deadline_ms": deadline_s * 1e3,
            "batch_size": batch,
            "models": sorted(inputs),
            "per_model_records": per_model,
            "legs": legs,
            "fixed_1x": leg_fixed,
            "p99_admitted_ms_10x": legs["10x"]["p99_ms"],
            "p99_bounded_10x": bool(
                legs["10x"]["p99_ms"] <= deadline_s * 1e3 + 50.0),
            "shed_rate_10x": legs["10x"]["shed_rate"],
            "occupancy_10x": legs["10x"]["occupancy"],
            "busy_s_total": round(busy_total, 3),
            "cross_model_compiles": churn.get("compiles", 0),
            "batch_spans_recorded": int(batch_spans)}


def bench_serving_fleet(smoke: bool) -> dict:
    """ROADMAP open item 1 (scale-out serving tier): a TRUE multi-process
    fleet — M spawned worker processes fanning over one Redis stream as a
    consumer group, N HTTP frontends enqueuing into it. Workers run a
    sleep-bound SleepModel (predict releases the GIL for ``batch_ms``), so
    per-worker capacity is batch_size/batch_ms by construction and the
    legs measure the TOPOLOGY (consumer-group fan-out, PEL reclaim, trace
    propagation) rather than this host's arithmetic: a compute-bound toy
    cannot scale across processes on a 1-core CI box, a chip-bound one
    does — exactly the shared-nothing regime real TPU workers are in.

    Legs: (1) single-worker saturated goodput g1; (2) M workers at M x the
    same offered load -> gM, gate gM >= 0.8 x M x g1 (smoke: 2 workers,
    >= 1.5 x g1); (3) 10x overload on one worker -> admitted p99 stays
    deadline-bounded (EDF shed valve); (4) SIGKILL one of two workers
    mid-run -> every request answered, lost == 0, survivor's PEL reclaim
    > 0, supervisor respawns; (5) two frontends + traced requests -> one
    trace id crosses frontend -> broker -> worker dispatch -> respond
    across the process boundary (span files dumped by workers on drain)."""
    import functools
    import json as _json
    import tempfile
    import threading
    import urllib.request

    from analytics_zoo_tpu.obs import trace as _trace
    from analytics_zoo_tpu.serving.fleet import ServingFleet, \
        sleep_model_factory
    from analytics_zoo_tpu.serving.http_frontend import create_app
    from analytics_zoo_tpu.serving.queue_api import make_broker
    from analytics_zoo_tpu.serving.redis_protocol import MiniRedisServer

    batch_ms, bs = 100.0, 4
    cap1 = bs / (batch_ms / 1e3)            # per-worker rps by construction
    n_workers = 2 if smoke else 4
    factory = functools.partial(sleep_model_factory, 2.0, batch_ms)
    vec = np.arange(64, dtype=np.float32)
    srv = MiniRedisServer(port=0)
    srv.start()
    host = f"127.0.0.1:{srv.port}"

    def fleet_for(stream, workers, worker_env=None, **kw):
        spec = f"redis://{host}/{stream}?claim_idle_ms=800"
        # host-only leg: the workers are pinned to the CPU explicitly, not
        # by whatever environment the parent happened to inherit
        kw["worker_env"] = {**_CHILD_ENV, **(worker_env or {})}
        fleet = ServingFleet(
            factory, spec, workers=workers, autoscale=False,
            batch_size=bs, batch_timeout_ms=20.0,
            # small per-worker admission bound: a worker may hold at most
            # ~2 batches, so the backlog stays ON the stream where every
            # consumer can claim it (the load-balancing half of the
            # shared-nothing contract)
            max_inflight=2 * bs,
            heartbeat_s=0.25, worker_ttl_s=2.0, drain_s=10.0, **kw)
        fleet.start()
        if not fleet.wait_live(workers, 60.0):
            raise RuntimeError(f"fleet {stream}: {workers} workers never "
                               f"went live: {fleet.metrics()}")
        return fleet, spec

    def run_leg(stream, workers, rate, dur_s, deadline_s, seed,
                kill_after_s=None, **kw):
        fleet, spec = fleet_for(stream, workers, **kw)
        broker = make_broker(spec)
        killer = None
        if kill_after_s is not None:
            killer = threading.Timer(kill_after_s, fleet.kill_worker)
            killer.daemon = True
            killer.start()
        try:
            leg = _serving_scale_leg(
                broker, {"default": vec}, rate,
                max(int(rate * dur_s), 2 * bs), deadline_s,
                np.random.RandomState(seed), n_fetchers=12)
        finally:
            if killer is not None:
                killer.cancel()
            snap = fleet.stop()
            broker.close()
        leg["workers"] = workers
        return leg, snap

    try:
        dur = 3.0 if smoke else 4.0
        # saturating offered load (1.5x capacity): goodput == what the
        # worker set actually serves, independent of generator pacing
        leg1, _ = run_leg("fl1", 1, 1.5 * cap1, dur, 2.5, 201)
        legN, _ = run_leg("flN", n_workers, 1.5 * cap1 * n_workers, dur,
                          2.5, 202)
        g1, gN = leg1["goodput_rps"], legN["goodput_rps"]
        linear_frac = gN / max(n_workers * g1, 1e-9)

        # 10x overload on one worker: EDF + deadline shed keep ADMITTED
        # p99 bounded while the shed valve absorbs the rest
        over_deadline = 0.6
        leg10, _ = run_leg("flo", 1, 10 * cap1, 1.5, over_deadline, 203)
        p99_bounded = bool(
            leg10["p99_ms"] <= over_deadline * 1e3 + 150.0)

        # chaos: SIGKILL one of two workers mid-run. The dead consumer's
        # pending entries idle out and the survivor's XAUTOCLAIM steals
        # them — every request answered, zero silently lost; the
        # supervisor respawns the dead slot
        chaos_rate = 0.6 * 2 * cap1
        leg_k, snap_k = run_leg("flc", 2, chaos_rate, 3.0, 8.0, 204,
                                kill_after_s=1.2)
        chaos = {"requests": leg_k["requests"], "ok": leg_k["ok"],
                 "shed": leg_k["shed"], "lost": leg_k["lost"],
                 "reclaimed": snap_k["reclaimed_total"],
                 "restarts": snap_k["restarts"]}

        # trace chain across processes: two frontends (N doors), traced
        # requests, workers dump their spans on drain; one trace id must
        # run frontend -> broker -> worker dispatch -> respond
        trace_dir = tempfile.mkdtemp(prefix="fleet_spans_")
        fleet_t, spec_t = fleet_for(
            "flt", 2, worker_env={"ZOO_TRACE": "1"}, trace_dir=trace_dir)
        fronts = []
        try:
            for _ in range(2):
                fronts.append(_frontend_thread(
                    create_app(spec_t, timeout_s=10.0, worker_ttl_s=2.0)))
            req_traces = set()
            with _trace.tracing(capacity=4096):
                for i in range(8):
                    port = fronts[i % 2][0]
                    body = _json.dumps(
                        {"instances": [vec.tolist()]}).encode()
                    r = urllib.request.urlopen(urllib.request.Request(
                        f"http://127.0.0.1:{port}/predict", data=body,
                        headers={"Content-Type": "application/json"}),
                        timeout=15)
                    assert r.status == 200, r.status
                ready = urllib.request.urlopen(
                    f"http://127.0.0.1:{fronts[0][0]}/readyz", timeout=5)
                assert ready.status == 200
                req_traces = {s.trace_id for s in _trace.spans()
                              if s.name == "serving.request"}
        finally:
            for _port, stop in fronts:
                stop()
            fleet_t.stop()
        worker_chains = {}
        for fn in os.listdir(trace_dir):
            with open(os.path.join(trace_dir, fn)) as f:
                for line in f:
                    s = _json.loads(line)
                    if s["name"] in ("serving.dispatch", "serving.respond"):
                        worker_chains.setdefault(
                            s["trace"], set()).add(s["name"])
        chained = [t for t in req_traces
                   if worker_chains.get(t) == {"serving.dispatch",
                                               "serving.respond"}]
        trace_chain_ok = bool(chained)
    finally:
        srv.stop()

    return {"metric": "serving_fleet_scaleout",
            "value": round(linear_frac, 3),
            "unit": f"x of linear 1->{n_workers}-worker goodput",
            "vs_baseline": round(linear_frac, 3),
            "baseline_note": "baseline = perfectly linear scaling from "
                             "the measured single-worker goodput "
                             "(shared-nothing ideal)",
            "workers": n_workers,
            "per_worker_capacity_rps": cap1,
            "goodput_1w_rps": g1,
            f"goodput_{n_workers}w_rps": gN,
            "scaleout_x": round(gN / max(g1, 1e-9), 3),
            "legs": {"1w": leg1, f"{n_workers}w": legN, "10x_1w": leg10,
                     "chaos_2w": leg_k},
            "p99_admitted_ms_10x": leg10["p99_ms"],
            "p99_bounded_10x": p99_bounded,
            "deadline_ms_10x": over_deadline * 1e3,
            "chaos": chaos,
            "frontends": 2,
            "trace_chain_ok": trace_chain_ok,
            "trace_ids_chained": len(chained),
            "trace_ids_requested": len(req_traces)}


def _frontend_thread(app):
    """Run an aiohttp app on an ephemeral port in a daemon thread; returns
    ``(port, stop)``. The fleet bench uses two of these as the N frontend
    doors of the scale-out topology."""
    import asyncio
    import threading

    from aiohttp import web

    loop = asyncio.new_event_loop()
    started = threading.Event()
    holder = {}
    runner = web.AppRunner(app)

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        holder["port"] = site._server.sockets[0].getsockname()[1]
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True, name="fleet-frontend")
    t.start()
    if not started.wait(15):
        raise RuntimeError("frontend thread failed to start")

    def stop():
        async def _cleanup():
            await runner.cleanup()
        asyncio.run_coroutine_threadsafe(_cleanup(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)

    return holder["port"], stop


def bench_attention(smoke: bool) -> dict:
    """Long-context attention: Pallas flash kernel (fwd + FA-2-style Pallas
    backward) vs materialized-scores reference attention on-chip, in bf16
    (training dtype) and f32. Compute-bound, so the numbers reflect the
    chip and the kernel, not host transfer. TFLOP/s are reported against
    the same-run achievable-ceiling matmul probe. The reference framework
    has only materialized attention (SURVEY.md §2.3: no flash/ring/
    sequence parallelism anywhere)."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.attention import flash_attention, mha_reference

    b, s, h, d = (2, 1024, 4, 64) if smoke else (4, 4096, 8, 64)
    rng = np.random.RandomState(0)
    base = [rng.rand(b, s, h, d).astype(np.float32) * 0.1 for _ in range(3)]
    flops_fwd = 4 * b * h * s * s * d / 2          # 2 matmuls, causal halves
    flops_bwd = flops_fwd * 3.5                    # fwd+bwd ~= 3.5x fwd

    # same-run achievable ceiling (a large bf16 matmul chain)
    @jax.jit
    def _mm_chain(a):
        return jax.lax.fori_loop(0, 8, lambda i, acc: acc @ a, a)
    mm = jax.device_put(jnp.ones((8192, 8192), jnp.bfloat16))
    float(_mm_chain(mm)[0, 0].astype(jnp.float32))
    ceiling = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        float(_mm_chain(mm)[0, 0].astype(jnp.float32))
        ceiling = max(ceiling, 2 * 8192**3 * 8 / (time.perf_counter() - t0))

    from jax import lax

    def chain_time(attn_fn, qkv, repeat, pipeline, grad):
        """Per-call seconds with per-dispatch overhead amortized away:
        ``repeat`` calls chained INSIDE one jit (output feeds the next
        call's q — real data dependence, like the ceiling probe's matmul
        chain) × ``pipeline`` non-blocking dispatches per timing, one
        fetch at the end — per-dispatch timing measures dispatch, not the
        kernel."""
        q0, k0, v0 = qkv

        if grad:
            @jax.jit
            def call(q, k, v):
                def loss(q, k, v):
                    return lax.fori_loop(
                        0, repeat,
                        lambda i, c: attn_fn(c.astype(q.dtype), k, v),
                        q).astype(jnp.float32).sum()
                return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)[0]
        else:
            @jax.jit
            def call(q, k, v):
                return lax.fori_loop(
                    0, repeat,
                    lambda i, c: attn_fn(c.astype(q.dtype), k, v), q)

        out = call(q0, k0, v0)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(3 if smoke else 5):
            t0 = time.perf_counter()
            o = q0
            for _ in range(pipeline):
                o = call(o.astype(q0.dtype), k0, v0)
            float(o[0, 0, 0, 0].astype(jnp.float32))
            best = min(best, (time.perf_counter() - t0))
        return best / (repeat * pipeline)

    def build(dtype):
        qkv = [jax.device_put(a.astype(dtype)) for a in base]
        flash = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa
        ref = lambda q, k, v: mha_reference(q, k, v, causal=True)  # noqa
        # flash chains deep (tiny memory); materialized keeps short chains
        # (its S^2 f32 scores are GB-scale per call, and its grad residuals
        # cap the chain at 1) — per-call work is large enough there that
        # residual dispatch slack is <15%
        return {
            "flash_fwd": chain_time(flash, qkv, 8, 4, False),
            "flash_grad": chain_time(flash, qkv, 4, 3, True),
            "ref_fwd": chain_time(ref, qkv, 2, 3, False),
            "ref_grad": chain_time(ref, qkv, 1, 3, True),
        }

    suites = {"bf16": build(jnp.bfloat16), "f32": build(jnp.float32)}

    detail = {}
    for dtname, t in suites.items():
        detail[dtname] = {
            "flash_ms": round(t["flash_fwd"] * 1e3, 2),
            "materialized_ms": round(t["ref_fwd"] * 1e3, 2),
            "speedup_fwd": round(t["ref_fwd"] / t["flash_fwd"], 2),
            "flash_fwd_bwd_ms": round(t["flash_grad"] * 1e3, 2),
            "materialized_fwd_bwd_ms": round(t["ref_grad"] * 1e3, 2),
            "speedup_fwd_bwd": round(t["ref_grad"] / t["flash_grad"], 2),
            "flash_tflops": round(flops_fwd / t["flash_fwd"] / 1e12, 2),
            "flash_fwd_bwd_tflops": round(
                flops_bwd / t["flash_grad"] / 1e12, 2),
            # denominator is the bf16 matmul probe for BOTH dtypes — the
            # f32 rows are understated relative to an f32 peak (the MXU
            # f32 rate is far lower); the key name says so
            "pct_of_bf16_achievable_fwd": round(
                100 * flops_fwd / t["flash_fwd"] / ceiling, 1),
            "pct_of_bf16_achievable_fwd_bwd": round(
                100 * flops_bwd / t["flash_grad"] / ceiling, 1),
            # like-for-like ceiling: at D=64 the score matmuls contract
            # over 64 of the MXU's 128 dims, so a perfect attention kernel
            # tops out at d/128 of the dense-matmul probe — this is the
            # structural roofline, not a kernel deficiency (demonstrated:
            # TFLOP/s doubles at D=128 for the same wall time)
            "pct_of_d64_roofline_fwd": round(
                100 * flops_fwd / t["flash_fwd"] /
                (ceiling * min(d, 128) / 128), 1),
            "pct_of_d64_roofline_fwd_bwd": round(
                100 * flops_bwd / t["flash_grad"] /
                (ceiling * min(d, 128) / 128), 1),
        }
    # long-context point: S=32k on one chip (materialized attention cannot
    # even compile there — the S^2 scores; flash stays O(S) memory and its
    # efficiency RISES with S as softmax state amortizes)
    long_seq = {}
    if not smoke:
        ls = 32768
        lrng = np.random.RandomState(1)
        qkv = [jax.device_put((lrng.rand(1, ls, h, d).astype(np.float32)
                               * 0.1).astype(jnp.bfloat16))
               for _ in range(3)]
        g = jax.jit(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        out = g(*qkv)
        float(jnp.sum(jax.tree_util.tree_leaves(out)[0][..., :1]
                      .astype(jnp.float32)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(3):
                out = g(*qkv)
            float(jnp.sum(jax.tree_util.tree_leaves(out)[0][..., :1]
                          .astype(jnp.float32)))
            best = min(best, (time.perf_counter() - t0) / 3)
        lf = 4 * 1 * h * ls * ls * d / 2 * 3.5
        long_seq = {"long_seq_len": ls,
                    "long_seq_fwd_bwd_ms": round(best * 1e3, 1),
                    "long_seq_fwd_bwd_tflops": round(lf / best / 1e12, 2)}

    bf = detail["bf16"]
    return {"metric": "flash_attention_speedup_vs_materialized",
            "value": bf["speedup_fwd_bwd"], "unit": "x",
            # reference framework has only the materialized form, so the
            # bf16 train-step (fwd+bwd) speedup IS the vs-baseline number
            "vs_baseline": bf["speedup_fwd_bwd"],
            "seq_len": s, "heads": h, "head_dim": d, "batch": b,
            "achievable_tflops_probe": round(ceiling / 1e12, 1),
            **{f"bf16_{k}": v for k, v in detail["bf16"].items()},
            **{f"f32_{k}": v for k, v in detail["f32"].items()},
            **long_seq}


def bench_compile_plane(smoke: bool) -> dict:
    """Compile-plane amortization: cold vs warm init+first-step.

    Builds an estimator and times init + first train dispatch twice —
    once cold (first compile of this program in the process; with
    ``ZOO_COMPILE_CACHE`` set, possibly a disk hit from a previous bench
    run) and once on a SECOND structurally identical estimator, whose
    first step reuses the cold run's executable through the shared cache.
    The warm-start delta is the per-object compile cost the plane removes
    from every additional engine (AutoML trial, serving worker, re-fit);
    on real TPU hardware the cold number is minutes, not seconds.
    """
    import flax.linen as nn
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator

    width = 64 if smoke else 256
    batch = 256 if smoke else 4096
    rng = np.random.RandomState(0)
    data = {"x": rng.rand(batch * 2, 32).astype(np.float32),
            "y": rng.rand(batch * 2).astype(np.float32)}

    class BenchMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = np.float32  # keep f32: the measurement is compile, not MXU
            for w in (width, width // 2):
                x = nn.relu(nn.Dense(w, dtype=h)(x))
            return nn.Dense(1, dtype=h)(x)[:, 0]

    def init_and_first_step() -> float:
        import jax
        est = TPUEstimator(BenchMLP(), loss="mse", optimizer="adam",
                           config={"steps_per_dispatch": 1})
        t0 = time.perf_counter()
        est.fit(data, epochs=1, batch_size=batch,
                steps_per_epoch=1, shuffle=False, verbose=False)
        jax.block_until_ready(est.engine.params)
        return time.perf_counter() - t0

    before = _compile_totals()
    cold_s = init_and_first_step()
    mid = _compile_totals()
    warm_s = init_and_first_step()
    after = _compile_totals()
    delta = round(cold_s - warm_s, 4)
    return {"metric": "compile_warm_start_speedup",
            "value": round(cold_s / max(warm_s, 1e-9), 2), "unit": "x",
            # no reference baseline exists (the reference compiles once per
            # job by construction); 1.0x = no amortization, so the speedup
            # itself is the vs-baseline signal
            "vs_baseline": round(cold_s / max(warm_s, 1e-9), 2),
            "cold_init_first_step_s": round(cold_s, 4),
            "warm_init_first_step_s": round(warm_s, 4),
            "warm_start_delta_s": delta,
            "cold_compile": _compile_delta(before, mid),
            "warm_compile": _compile_delta(mid, after),
            "persistent_dir": os.environ.get("ZOO_COMPILE_CACHE") or None}


def bench_infeed(smoke: bool) -> dict:
    """Transfer-plane microbench: narrow uint8 wire + on-device prologue
    vs the host-side f32 path it replaces, through the PRODUCTION input
    pipeline (chunked assembler → InfeedPump lanes → sharded device_put →
    jitted step with prologue).

    Reports the bytes-per-sample reduction (the ``value``; uint8 images
    cut H2D 4x), asserts the two paths train BIT-IDENTICALLY (same seed →
    same losses — normalize-in-f32 on device equals normalize-in-f32 on
    host), and carries both runs' ``data_pipeline_stats`` snapshots
    (per-stage MB/s, lanes, ``transfer_limited`` verdict). CPU-friendly:
    CI runs this as the wire-format regression gate
    (.github/workflows/tier1.yml).
    """
    import flax.linen as nn
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.prologue import (BatchPrologue,
                                                       image_normalize)

    side = 16 if smoke else 32
    batch = 64 if smoke else 256
    n = batch * (8 if smoke else 16)
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (n, side, side, 3), np.uint8)
    # int64 labels on purpose: the wire narrows them to their canonical
    # int32 device form (half the label bytes for identical device bits)
    labels = rng.randint(0, 10, n).astype(np.int64)

    class TinyNet(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(64)(x))
            return nn.Dense(10)(x)

    prol = BatchPrologue(x=(image_normalize(),))

    def run(data_x, data_y, prologue):
        est = TPUEstimator(TinyNet(), loss="sparse_categorical_crossentropy",
                           optimizer="adam",
                           config={"steps_per_dispatch": 1},
                           prologue=prologue)
        stats = est.fit({"x": data_x, "y": data_y}, epochs=2,
                        batch_size=batch, shuffle=True, verbose=False)
        return [s["train_loss"] for s in stats], est.data_pipeline_stats()

    narrow_losses, narrow_stats = run(imgs, labels, prol)
    f32_losses, f32_stats = run(prol.host_x((imgs,))[0],
                                labels.astype(np.int32), None)

    samples = 2 * n
    wire_bps = narrow_stats["h2d_bytes"] / samples
    f32_bps = f32_stats["h2d_bytes"] / samples
    reduction = f32_bps / max(wire_bps, 1e-9)
    return {"metric": "infeed_wire_byte_reduction",
            "value": round(reduction, 2), "unit": "x",
            # no reference baseline (the reference always ships f32 after
            # host-side normalize) — the reduction IS the vs-baseline signal
            "vs_baseline": round(reduction, 2),
            "bit_identical": bool(narrow_losses == f32_losses),
            "wire_bytes_per_sample": round(wire_bps, 1),
            "f32_bytes_per_sample": round(f32_bps, 1),
            "transfer_limited": narrow_stats["transfer_limited"],
            "lanes": narrow_stats["lanes"],
            "h2d_MBps": narrow_stats["h2d_MBps"],
            "data_pipeline_stats": narrow_stats,
            "f32_data_pipeline_stats": f32_stats,
            "batch": batch, "n": n, "image_side": side}


def _sharding_child(smoke: bool) -> dict:
    """Runs inside the 8-device simulated CPU mesh subprocess: the sharding
    plane (PR 17) through the production estimator. Two legs:

    * fsdp×tp bit-identity + accounting (dp=1, fsdp=4, tp=2): the SAME
      mesh trains the same model with the plane on and off — SGD losses,
      canonical checkpoint params and served predictions must match BIT
      FOR BIT (fsdp gathers and tp row/column matmuls are elementwise-
      order-preserving; adam is excluded from the gate because XLA fuses
      its sqrt/div chain program-dependently, ~1 ulp). Collective
      launches/bytes are counted per mesh axis in the COMPILED program
      (sharding collectives only exist post-SPMD-partitioner) and
      cross-checked against the engine's declared accounting by the
      hlo_lint rule itself.

    * the headline capacity leg (dp=1, fsdp=8): a model whose param+adam
      state is ~4× ``SIM_CHIP_HBM_BYTES`` (the simulated one-chip bound)
      trains AND serves with every device holding < the bound — the
      "models bigger than one chip" acceptance proof, measured from the
      devices' addressable shards, not declared.
    """
    import flax.linen as nn
    import jax

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.analysis.hlo_lint import (HloLinter,
                                                     collective_counts,
                                                     collectives_by_mesh_axes,
                                                     declared_accounting,
                                                     parse_collectives)
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    from analytics_zoo_tpu.parallel.sharding import SpecLayout
    from analytics_zoo_tpu.parallel.tensor_parallel import TPMLP
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel

    init_orca_context("cpu-sim", mesh_axes={"dp": 1, "fsdp": 4, "tp": 2})
    # simulated one-chip HBM bound: the capacity leg's model is sized ~4x
    # this, so "fits" is a real <, not a tautology
    chip_bound = (1 if smoke else 8) * (1 << 20)
    big_width = 592 if smoke else 1696
    width = 32 if smoke else 64
    n = 512 if smoke else 1024
    epochs = 2

    class TPNet(nn.Module):
        # one tp block between plain Dense layers: the fsdp flat vector
        # and the tp row/column kernels coexist in one param tree
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(width)(x))
            x = TPMLP(width * 2, out_dim=width, name="tp_mlp")(x)
            return nn.Dense(1)(x)[:, 0]

    class BigMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(big_width)(x))
            x = nn.relu(nn.Dense(big_width)(x))
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    data = {"x": rng.rand(n, 16).astype(np.float32),
            "y": rng.rand(n).astype(np.float32)}

    def run(mesh, model, sharding, optimizer="sgd"):
        est = TPUEstimator(model, loss="mse", optimizer=optimizer, seed=0,
                           mesh=mesh, config={"steps_per_dispatch": 1},
                           sharding=sharding)
        it = data_to_iterator(dict(data), 64, est.mesh, None, None,
                              shuffle=False, config=est.config)
        b0 = next(it.epoch(shuffle=False, prefetch=False))
        est.engine.build(tuple(np.asarray(a) for a in b0.x))
        fn = est.engine.ensure_jit_train()
        args = est.engine.train_step_args(b0)
        # sharding collectives exist only POST-partitioner: count them in
        # the compiled program, not the lowered StableHLO
        text = fn.lower(*args).compile().as_text()
        axes = {a: int(s) for a, s in est.engine.mesh.shape.items()
                if int(s) > 1}
        bya = collectives_by_mesh_axes(parse_collectives(text), axes)
        declared = (declared_accounting(est.engine._sharding_key())
                    if sharding is not False else None)
        accounting_ok = (not HloLinter().lint_text(
            text, label="bench:train", declared=declared)
            if declared else None)
        t0 = time.perf_counter()
        stats = est.fit(dict(data), epochs=epochs, batch_size=64,
                        verbose=False)
        dt = time.perf_counter() - t0
        state = est.engine.get_state()     # CANONICAL tree form both ways
        weights = np.concatenate(
            [np.asarray(l).ravel() for l in
             jax.tree_util.tree_leaves(state["params"])])
        full_bytes = sum(
            int(l.nbytes) for l in
            jax.tree_util.tree_leaves(est.engine.params)
            + jax.tree_util.tree_leaves(est.engine.opt_state))
        return {"est": est, "params": state["params"],
                "losses": [s["train_loss"] for s in stats],
                "weights": weights, "by_axes": bya,
                "declared": declared, "accounting_verified": accounting_ok,
                "full_state_bytes": full_bytes,
                "per_device_state_bytes":
                    est.engine.per_device_state_bytes(),
                "fit_s": round(dt, 3)}

    def served_per_device_bytes(model):
        return sum(int(s.data.nbytes) for leaf in
                   jax.tree_util.tree_leaves(model._variables)
                   for s in leaf.addressable_shards[:1])

    # --- leg 1: fsdp×tp bit-identity + per-axis accounting ------------------
    mesh42 = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    tpnet = TPNet()
    shd = run(mesh42, tpnet, SpecLayout())
    rep = run(mesh42, tpnet, False)
    train_bitid = bool(shd["losses"] == rep["losses"]
                       and shd["weights"].shape == rep["weights"].shape
                       and (shd["weights"] == rep["weights"]).all())
    # serve both layouts from the canonical trained params on the same mesh
    xq = rng.rand(24, 16).astype(np.float32)
    im_s = InferenceModel(mesh=mesh42, sharding=SpecLayout()).load_jax(
        tpnet, {"params": shd["params"]})
    im_r = InferenceModel(mesh=mesh42).load_jax(
        tpnet, {"params": rep["params"]})
    ps, pr = im_s.predict(xq), im_r.predict(xq)
    serve_bitid = bool((np.asarray(ps) == np.asarray(pr)).all())

    d = shd["declared"]["fsdp"]
    fsdp_ops = shd["by_axes"]["by_axis"].get("fsdp", {})
    fsdp_bytes = shd["by_axes"]["axis_bytes"].get("fsdp", {})
    ag = fsdp_ops.get("all_gather", 0)
    sweeps = ag // max(d["buckets"], 1)
    gather_bytes = fsdp_bytes.get("all_gather", 0)
    tp_ar = shd["by_axes"]["by_axis"].get("tp", {}).get("all_reduce", 0)

    # --- leg 2: the 4×-HBM capacity proof (train + serve) -------------------
    mesh8 = create_mesh({"dp": 1, "fsdp": -1})
    big = BigMLP()
    cap = run(mesh8, big, SpecLayout(), optimizer="adam")
    im_big = InferenceModel(mesh=mesh8, sharding=SpecLayout()).load_jax(
        big, {"params": cap["params"]})
    big_pred = im_big.predict(xq)
    serve_dev_bytes = served_per_device_bytes(im_big)
    over = cap["full_state_bytes"] / chip_bound

    return {
        "metric": "sharding_model_over_chip_hbm",
        "value": round(over, 2), "unit": "x",
        # no reference baseline (the reference replicated the model per
        # worker; a model over one worker's memory simply did not run) —
        # the capacity multiple IS the vs-baseline signal
        "vs_baseline": round(over, 2),
        "train_bit_identical": train_bitid,
        "serve_bit_identical": serve_bitid,
        "losses_equal": bool(shd["losses"] == rep["losses"]),
        "accounting_verified": bool(shd["accounting_verified"]),
        "capacity_accounting_verified": bool(cap["accounting_verified"]),
        "fsdp_buckets": d["buckets"],
        "fsdp_gather_launches": ag,
        "fsdp_gather_sweeps": sweeps,
        "fsdp_gather_bytes": gather_bytes,
        "gather_bytes_match_declared": bool(
            sweeps >= 1 and ag == sweeps * d["buckets"]
            and gather_bytes
            == sweeps * d["gather_shard_bytes_per_sweep"]),
        "fsdp_grad_combine_launches":
            fsdp_ops.get("all_reduce", 0)
            + fsdp_ops.get("reduce_scatter", 0),
        "tp_all_reduce_launches": tp_ar,
        "tp_present": bool(tp_ar >= 1),
        "chip_bound_bytes": chip_bound,
        "full_state_bytes": cap["full_state_bytes"],
        "per_device_state_bytes": cap["per_device_state_bytes"],
        "replicated_exceeds_chip": bool(
            cap["full_state_bytes"] > chip_bound),
        "sharded_fits_chip": bool(
            cap["per_device_state_bytes"] < chip_bound),
        "sharding_factor": round(cap["full_state_bytes"]
                                 / cap["per_device_state_bytes"], 2),
        "serve_per_device_weight_bytes": serve_dev_bytes,
        "serve_fits_chip": bool(serve_dev_bytes < chip_bound),
        "serve_pred_finite": bool(np.isfinite(big_pred).all()),
        "capacity_loss_finite": bool(
            np.isfinite(cap["losses"]).all()),
        "fit_s": {"fsdp_tp_sharded": shd["fit_s"],
                  "fsdp_tp_replicated": rep["fit_s"],
                  "capacity_fsdp8": cap["fit_s"]},
        "mesh_axes": {"bitid": {"fsdp": 4, "tp": 2},
                      "capacity": {"fsdp": 8}},
    }


def bench_sharding(smoke: bool) -> dict:
    """Sharding-plane microbench (PR 17): fsdp×tp SpecLayout through the
    production estimator + InferenceModel on a SIMULATED 8-device CPU
    mesh (subprocess — the bench process's device count is fixed at jax
    import).

    CI gates on: sharded training and serving bit-identical to the
    replicated layout on the SAME mesh (SGD — elementwise-safe math),
    hlo_lint's per-axis accounting verified against the engine's declared
    summary (fsdp gather launches in whole sweeps of the bucket count,
    gather bytes == sweeps × declared shard bytes, tp all-reduce
    present), and the capacity leg: a model ~4× the simulated one-chip
    HBM bound trains AND serves with per-device param+optimizer bytes
    under the bound (.github/workflows/tier1.yml).
    """
    import re
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # each leg configures its plane explicitly — ambient sharding knobs
    # would contaminate the replicated baseline
    for knob in ("ZOO_SHARDING_PLANE", "ZOO_FSDP_BUCKET_MB",
                 "ZOO_MESH_AXES"):
        env.pop(knob, None)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_sharding_child",
         "1" if smoke else "0"],
        env=env, capture_output=True, text=True, timeout=900)
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"sharding child failed (rc={proc.returncode}): "
            f"{proc.stderr.strip()[-2000:]}")
    return json.loads(lines[-1])


def bench_ckpt(smoke: bool) -> dict:
    """Checkpoint-plane microbench: async save stall vs the blocking write
    at NCF scale, dedup ratio, atomic-commit crash resume.

    Builds the NCF estimator state (params + Adam moments — the blob the
    old path pickled synchronously every trigger) and measures:

    * ``blocking_save_s`` — full inline save (snapshot + hash + blobs +
      fsync + commit), the old stall the loop used to pay;
    * ``async_stall_s`` — what the loop pays on the plane (device→host
      snapshot + skeleton pickle; hashing/IO drain on the writer thread).
      Acceptance gate: stall < 20% of the blocking time;
    * ``dedup_ratio`` — re-saving an unchanged state writes ~0 new bytes;
    * ``bit_identical`` — async and blocking saves of one state produce
      identical per-leaf digests and restore to identical trees;
    * ``crash_resume_ok`` — a torn (uncommitted) newer dir is invisible:
      the loader lands on the last committed checkpoint.

    CPU-friendly; CI runs this as the checkpoint smoke gate (tier1.yml).
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ckpt import CheckpointPlane, read_manifest
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    from analytics_zoo_tpu.orca.learn.optimizers import Adam

    n_users, n_items = (600, 370) if smoke else (6040, 3706)
    embed = 16 if smoke else 64
    batch = 256
    rng = np.random.RandomState(0)
    pairs = np.stack([rng.randint(1, n_users, batch * 2),
                      rng.randint(1, n_items, batch * 2)],
                     -1).astype(np.int32)
    ratings = rng.randint(0, 5, batch * 2).astype(np.int32)
    model = NeuralCF(user_count=n_users, item_count=n_items, class_num=5,
                     user_embed=embed, item_embed=embed,
                     hidden_layers=(embed * 2, embed), mf_embed=embed)
    model.compile(loss="sparse_categorical_crossentropy",
                  optimizer=Adam(lr=1e-3), metrics=None)
    est = model.estimator
    est.fit({"x": pairs, "y": ratings}, epochs=1, batch_size=batch,
            verbose=False)
    state = est.engine.get_state()
    state_mb = sum(np.asarray(l).nbytes
                   for l in jax.tree_util.tree_leaves(state)
                   if hasattr(l, "nbytes")) / 1e6

    def perturbed(k: int):
        # fresh bytes per save, so dedup can't make later saves free and
        # the blocking-vs-async comparison stays apples-to-apples
        return dict(state, params=jax.tree_util.tree_map(
            lambda a: np.asarray(a) + np.float32(1e-3 * (k + 1)),
            jax.device_get(state["params"])))

    root = tempfile.mkdtemp(prefix="zoo-ckpt-bench-")
    try:
        reps = 3
        blk = CheckpointPlane(os.path.join(root, "blocking"),
                              async_save=False)
        blocking = []
        for k in range(reps):
            s = perturbed(k)
            t0 = time.perf_counter()
            blk.save(s, k)
            blocking.append(time.perf_counter() - t0)
        blocking_s = sorted(blocking)[reps // 2]

        asy = CheckpointPlane(os.path.join(root, "async"), max_inflight=2)
        stalls = []
        for k in range(reps):
            s = perturbed(k)
            t0 = time.perf_counter()
            asy.save(s, k)
            stalls.append(time.perf_counter() - t0)
            asy.flush()             # isolate each save's stall
        stall_s = sorted(stalls)[reps // 2]
        hidden_s = asy.stats.snapshot()["hidden_s"] / reps

        # bit-identity: one identical state through both writer paths
        same = perturbed(99)
        da = asy.save(same, 99)
        asy.flush()
        db = blk.save(same, 99)
        ma, mb = read_manifest(da), read_manifest(db)
        bit_identical = (
            [l["digest"] for l in ma["leaves"]]
            == [l["digest"] for l in mb["leaves"]]
            and ma["skeleton"]["digest"] == mb["skeleton"]["digest"])

        # dedup: unchanged state re-saved -> ~no new bytes
        ddup = CheckpointPlane(os.path.join(root, "dedup"),
                               async_save=False)
        ddup.save(same, 1)
        ddup.save(same, 2)
        dedup_ratio = ddup.stats.snapshot()["dedup_ratio"]

        # crash injection: a newer dir without COMMIT must be skipped
        torn = os.path.join(root, "dedup", "ckpt-3")
        os.makedirs(torn)
        with open(os.path.join(torn, "MANIFEST.json"), "w") as f:
            f.write("{}")           # torn write: manifest, no COMMIT
        path, got = ddup.restore()
        crash_resume_ok = path.endswith("ckpt-2") and bool(
            np.array_equal(
                jax.tree_util.tree_leaves(got["params"])[0],
                jax.tree_util.tree_leaves(same["params"])[0]))
        asy.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    stall_frac = stall_s / max(blocking_s, 1e-9)
    return {"metric": "ckpt_async_save_hiding",
            "value": round(blocking_s / max(stall_s, 1e-9), 2), "unit": "x",
            # no reference baseline (the reference pickles synchronously);
            # the hiding factor IS the vs-baseline signal
            "vs_baseline": round(blocking_s / max(stall_s, 1e-9), 2),
            "async_stall_frac_of_blocking": round(stall_frac, 4),
            "stall_lt_20pct": bool(stall_frac < 0.20),
            "blocking_save_s": round(blocking_s, 5),
            "async_stall_s": round(stall_s, 5),
            "hidden_write_s": round(hidden_s, 5),
            "dedup_ratio": dedup_ratio,
            "bit_identical": bool(bit_identical),
            "crash_resume_ok": bool(crash_resume_ok),
            "state_mb": round(state_mb, 2)}


def bench_resilience(smoke: bool) -> dict:
    """Resilience-plane chaos microbench: injected mid-fit H2D fault →
    supervisor auto-recovery, plus serving deadline shedding.

    Training half: a fault-free ``fit(epochs=E)`` provides the reference
    weights, then a :class:`TrainingSupervisor` runs the same training with
    a one-shot ``h2d.put`` fault injected mid-run. Reported: ``downtime_s``
    (teardown + rebuild + restore wall time), ``steps_replayed`` (optimizer
    steps between the restored checkpoint and the failure point — work the
    fault cost), ``restarts``, and ``bit_identical`` — the recovered run's
    final params must equal the fault-free run's bit for bit (the CI chaos
    gate).

    Serving half: a mix of expired and live requests through
    ``ClusterServing`` — expired ones must be shed with an error result
    *before* device dispatch (``expired_never_dispatched``: the model saw
    exactly the live records).
    """
    import shutil
    import tempfile

    import flax.linen as nn
    import jax
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.resilience import TrainingSupervisor, faults
    from analytics_zoo_tpu.serving import ClusterServing, InMemoryBroker
    from analytics_zoo_tpu.serving.codecs import (decode_payload,
                                                  encode_payload)

    class _Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    n = 128 if smoke else 512
    data = {"x": rng.rand(n, 8).astype(np.float32),
            "y": rng.rand(n).astype(np.float32)}
    epochs, batch = (3, 32)

    def make_est(model_dir=None):
        return TPUEstimator(_Net(), loss="mse", optimizer="adam",
                            model_dir=model_dir, seed=0,
                            config={"steps_per_dispatch": 1})

    root = tempfile.mkdtemp(prefix="zoo-resilience-bench-")
    try:
        # reference: uninterrupted, unsupervised
        ref = make_est()
        ref.fit(dict(data), epochs=epochs, batch_size=batch, verbose=False)
        ref_leaves = jax.tree_util.tree_leaves(
            jax.device_get(ref.engine.get_state()["params"]))

        sup = TrainingSupervisor(lambda: make_est(root), model_dir=root,
                                 max_restarts=3)
        # one-shot H2D fault mid-run: skip past epoch 1's transfers so the
        # recovery really replays from a non-trivial checkpoint
        steps = n // batch
        with faults.inject("h2d.put", count=1, skip=3 * steps):
            t0 = time.perf_counter()
            report = sup.fit(dict(data), epochs=epochs, batch_size=batch)
            wall_s = time.perf_counter() - t0
        got_leaves = jax.tree_util.tree_leaves(jax.device_get(
            sup.estimator.engine.get_state()["params"]))
        bit_identical = len(ref_leaves) == len(got_leaves) and all(
            np.array_equal(a, b) for a, b in zip(ref_leaves, got_leaves))
        sup.estimator.shutdown()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # serving overload: expired requests shed before device dispatch
    class _CountingModel:
        def __init__(self):
            self.seen = 0

        def predict(self, x):
            self.seen += int(np.asarray(x).shape[0])
            return np.asarray(x) * 2.0

    model = _CountingModel()
    broker = InMemoryBroker()
    cs = ClusterServing(model, queue=broker, batch_size=8,
                        batch_timeout_ms=5.0)
    n_expired, n_live = 4, 4
    for i in range(n_expired):
        broker.enqueue(f"x{i}", encode_payload(
            np.ones(3, np.float32), meta={"deadline": time.time() - 1.0}))
    for i in range(n_live):
        broker.enqueue(f"l{i}", encode_payload(
            np.ones(3, np.float32), meta={"deadline": time.time() + 30.0}))
    cs.start()
    live_ok = expired_shed = 0
    for i in range(n_live):
        raw = broker.get_result(f"l{i}", timeout_s=10.0)
        arr, meta = decode_payload(raw)
        live_ok += int(not meta.get("error"))
    for i in range(n_expired):
        raw = broker.get_result(f"x{i}", timeout_s=10.0)
        _, meta = decode_payload(raw)
        expired_shed += int(meta.get("shed") == "expired")
    serving_res = cs.metrics()["resilience"]
    cs.stop()
    expired_never_dispatched = model.seen == n_live

    return {"metric": "resilience_recovery_downtime",
            "value": round(report["downtime_s"], 4), "unit": "s",
            "vs_baseline": 1.0,     # no reference analogue (Spark reran
            "restarts": report["restarts"],         # whole stages instead)
            "hangs": report["hangs"], "crashes": report["crashes"],
            "steps_replayed": report["steps_replayed"],
            "downtime_s": round(report["downtime_s"], 4),
            "supervised_wall_s": round(wall_s, 3),
            "bit_identical": bool(bit_identical),
            "completed": bool(report["completed"]),
            "shed_expired": serving_res["shed_expired"],
            "live_served_ok": live_ok,
            "expired_shed_results": expired_shed,
            "expired_never_dispatched": bool(expired_never_dispatched),
            "breaker_state": serving_res["breaker"]["state"],
            "ok": bool(bit_identical and report["restarts"] >= 1
                       and expired_never_dispatched)}


def bench_obs(smoke: bool) -> dict:
    """Observability-plane microbench: disarmed and armed tracing overhead
    on the NCF smoke loop + exposition round-trips.

    The NCF training loop (the same per-dispatch loop ``bench_ncf`` times)
    runs twice — tracing disarmed, then armed — and the hook cost is
    additionally measured directly: N disarmed ``trace.span(...)`` calls
    timed and scaled by the hooks a production step passes (engine
    dispatch + two infeed-lane sites + the ckpt token capture). The scaled
    hook cost over the measured step time is ``disarmed_overhead_frac`` —
    the CI gate asserts it under 1% (the wall-clock A/B delta is reported
    too, but CPU smoke noise makes the direct measurement the gate).
    Also validated: the Prometheus text exposition parses with the strict
    mini-parser and the armed run's span ring exports as well-formed
    Chrome/Perfetto ``trace_event`` JSON with ≥1 span per step.
    """
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.common.context import get_context
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    from analytics_zoo_tpu.obs import prometheus_text, trace
    from analytics_zoo_tpu.obs.export import parse_exposition, perfetto_trace
    from analytics_zoo_tpu.orca.learn.optimizers import Adam
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    ctx = get_context()
    n_users, n_items = (600, 370) if smoke else (6040, 3706)
    batch = 1024 if smoke else 8192
    steps = 10 if smoke else 30

    rng = np.random.RandomState(0)
    n = batch * 4
    pairs = np.stack([rng.randint(1, n_users, n),
                      rng.randint(1, n_items, n)], -1).astype(np.int32)
    ratings = rng.randint(0, 5, n).astype(np.int32)
    model = NeuralCF(user_count=n_users, item_count=n_items, class_num=5,
                     user_embed=16, item_embed=16, hidden_layers=(32, 16),
                     mf_embed=16, compute_dtype=jnp.bfloat16)
    model.compile(loss="sparse_categorical_crossentropy",
                  optimizer=Adam(lr=1e-3), metrics=None)
    est = model.estimator
    it = data_to_iterator({"x": pairs, "y": ratings}, batch, ctx.mesh,
                          shuffle=True)
    est.engine.build((pairs[:1],))
    hb = []
    for b in it._host_batches(True):
        hb.append(b)
        if len(hb) >= 4:
            break
    float(est.engine.train_batch(hb[0]))    # compile + warm
    float(est.engine.train_batch(hb[0]))

    def loop() -> float:
        t0 = time.perf_counter()
        for i in range(steps):
            loss = est.engine.train_batch(hb[i % len(hb)])
        float(loss)     # value fetch forces the whole chain (see header)
        return (time.perf_counter() - t0) / steps

    was_armed = trace.enabled()
    trace.disarm()
    dt_disarmed = min(loop(), loop())
    trace.clear()
    with trace.tracing():
        dt_armed = min(loop(), loop())
        spans = trace.spans()
    dispatch_spans = [s for s in spans if s.name == "engine.dispatch"]
    spans_per_step = len(dispatch_spans) / (2 * steps)

    # direct hook cost: the disarmed fast path is one module-global flag
    # check returning the shared no-op (same discipline as faults.fire).
    # Tracing must stay DISARMED for this loop — re-arming first (e.g.
    # under ZOO_TRACE_PERFETTO) would measure live spans and flood the
    # ring with 200k zero-work records
    n_calls = 200_000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        with trace.span("engine.dispatch", step=0):
            pass
    per_call = (time.perf_counter() - t0) / n_calls
    if was_armed:
        trace.arm()
    hooks_per_step = 4      # dispatch span + 2 infeed-lane spans + token()
    disarmed_frac = per_call * hooks_per_step / max(dt_disarmed, 1e-9)

    try:
        prom = parse_exposition(prometheus_text())
        prom_ok, prom_samples = True, len(prom)
    except ValueError:
        prom_ok, prom_samples = False, 0
    doc = perfetto_trace(spans)
    perfetto_ok = bool(doc["traceEvents"]) and all(
        {"ph", "name", "pid", "tid"} <= set(e)
        and (e["ph"] != "X" or ("ts" in e and "dur" in e))
        for e in doc["traceEvents"])

    wall_delta = dt_armed / max(dt_disarmed, 1e-9) - 1.0
    return {"metric": "obs_disarmed_overhead",
            "value": round(disarmed_frac * 100, 5), "unit": "%",
            # no reference analogue (the reference's metrics ride Flink's
            # own reporters); the gate IS the signal
            "vs_baseline": 1.0,
            "disarmed_overhead_frac": round(disarmed_frac, 7),
            "disarmed_overhead_lt_1pct": bool(disarmed_frac < 0.01),
            "disarmed_hook_ns": round(per_call * 1e9, 1),
            "armed_wall_overhead_frac": round(wall_delta, 4),
            "step_s_disarmed": round(dt_disarmed, 6),
            "step_s_armed": round(dt_armed, 6),
            "spans_recorded": len(spans),
            "spans_per_step": round(spans_per_step, 2),
            "prom_parse_ok": bool(prom_ok),
            "prom_samples": prom_samples,
            "perfetto_ok": bool(perfetto_ok),
            "ok": bool(disarmed_frac < 0.01 and prom_ok and perfetto_ok
                       and spans_per_step >= 1.0)}


# legs that measure the chip: any platform but tpu is refused
DEVICE_LEGS = ("resnet50", "ncf", "fraud_mlp", "autots", "serving_od",
               "attention")
# legs whose JAX work, if any, happens in child processes started with an
# explicit JAX_PLATFORMS=cpu while the parent stays off JAX: they run FIRST
# (a parent that has touched JAX holds the chip) and are labeled without
# asking JAX for its devices
HOST_ONLY_LEGS = ("serving_fleet", "shm", "sharding")
_HOST_ONLY_LABEL = {"platform": "cpu", "device_kind": "cpu",
                    "device_count": 0,
                    "children_platform": _CHILD_ENV["JAX_PLATFORMS"]}


def main():
    if "--_sharding_child" in sys.argv:
        # bench_sharding's simulated-mesh subprocess — one JSON line
        pos = sys.argv.index("--_sharding_child") + 1
        smoke = pos < len(sys.argv) and sys.argv[pos] == "1"
        print(json.dumps(_sharding_child(smoke)))
        return
    # CLI flags mirror the env knobs (CI uses the flags):
    #   --smoke           == BENCH_SMOKE=1 (reduced workloads)
    #   --only a,b        == BENCH_ONLY=a,b (subset of workloads)
    smoke = bool(int(os.environ.get("BENCH_SMOKE", "0"))) \
        or "--smoke" in sys.argv
    only = os.environ.get("BENCH_ONLY", "").split(",") if \
        os.environ.get("BENCH_ONLY") else None
    if "--only" in sys.argv:
        pos = sys.argv.index("--only") + 1
        if pos >= len(sys.argv):
            print("usage: bench.py [--smoke] [--only workload[,workload...]]",
                  file=sys.stderr)
            sys.exit(2)
        only = sys.argv[pos].split(",")

    # no context is created here: the first leg that needs JAX makes one
    # (get_context), so the HOST_ONLY legs run with the parent off JAX
    benches = {"serving_fleet": bench_serving_fleet, "shm": bench_shm,
               "sharding": bench_sharding,
               "streaming_fleet": bench_streaming_fleet,
               "resnet50": bench_resnet50, "ncf": bench_ncf,
               "fraud_mlp": bench_fraud_mlp, "autots": bench_autots_trials,
               "serving_od": bench_serving_od,
               "serving_scale": bench_serving_scale,
               "attention": bench_attention,
               "compile_plane": bench_compile_plane,
               "infeed": bench_infeed, "ckpt": bench_ckpt,
               "resilience": bench_resilience,
               "obs": bench_obs, "streaming": bench_streaming}
    # smoke runs must never clobber full-run artifacts (vs_baseline on a
    # reduced workload against a full-scale baseline is meaningless)
    detail_name = "BENCH_DETAIL_SMOKE.json" if smoke else "BENCH_DETAIL.json"
    detail_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               detail_name)
    # merge into the existing record: a BENCH_ONLY partial run must not
    # clobber the other workloads' stored results
    detail = {}
    if os.path.exists(detail_path):
        try:
            with open(detail_path) as f:
                detail = json.load(f)
        except Exception:
            detail = {}
    detail.pop("smoke", None)   # provenance is per-entry now
    failed = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        compile_before = _compile_totals()
        try:
            if name in DEVICE_LEGS:
                _require_tpu(name)
            detail[name] = fn(smoke)
        except Exception as e:
            # the other legs still run and the detail file still records
            # them, but the run as a whole has failed: exit code below
            import traceback
            traceback.print_exc()
            detail[name] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(name)
        if isinstance(detail[name], dict):
            detail[name]["smoke"] = smoke
            detail[name].update(_HOST_ONLY_LABEL if name in HOST_ONLY_LEGS
                                else _device_label())
            # per-workload compile attribution: compiles paid vs executables
            # reused (in-process or from the disk cache) during this bench
            stats = _compile_delta(compile_before, _compile_totals())
            detail[name].setdefault("compile_stats", stats)
            print(f"{name} compile_stats:", json.dumps(stats))

    with open(detail_path, "w") as f:
        json.dump(detail, f, indent=2)

    resnet_res = detail.get("resnet50", {})
    out = dict(resnet_res) if "error" not in resnet_res else {}
    out.pop("step_flops", None)
    for name, key in (("ncf", "ncf"), ("fraud_mlp", "fraud_mlp"),
                      ("autots", "autots"), ("serving_od", "serving_od"),
                      ("serving_scale", "serving_scale"),
                      ("serving_fleet", "serving_fleet"),
                      ("attention", "flash_attention_speedup"),
                      ("compile_plane", "compile_warm_start"),
                      ("infeed", "infeed_wire_reduction"),
                      ("ckpt", "ckpt_async_hiding"),
                      ("sharding", "sharding_model_over_chip"),
                      ("obs", "obs_disarmed_overhead"),
                      ("streaming", "streaming_records_per_s"),
                      ("streaming_fleet", "streaming_fleet")):
        r = detail.get(name, {})
        if r and "error" not in r:
            out[f"{key}_value"] = r["value"]
            out[f"{key}_vs_baseline"] = r["vs_baseline"]
            for extra in ("compute_samples_per_sec_per_chip",
                          "compute_vs_baseline", "mfu_compute"):
                if extra in r and r[extra] is not None:
                    out[f"{key}_{extra.replace('_samples_per_sec_per_chip', '')}"] = r[extra]
    print(json.dumps(out))
    if failed:
        print(f"bench: failed legs: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
