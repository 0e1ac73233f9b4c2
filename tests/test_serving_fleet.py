"""Scale-out serving tier (fleet): consumer-group parity across broker
transports, the autoscaler control loop in isolation, frontend fleet
health / queue-age shed, and the multi-process ServingFleet supervisor
(SIGKILL chaos -> PEL reclaim, occupancy-driven autoscaling).

The parity tests are the satellite contract that lets every fleet test
run WITHOUT a Redis server: InMemory and File brokers must match the
Redis consumer-group semantics — disjoint claims across consumers,
entries pending until result/ack, XAUTOCLAIM-style idle reclaim of a
dead consumer's pending entries, heartbeats through the broker.
"""

import functools
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.serving.fleet import (Autoscaler, ServingFleet,
                                             SleepModel,
                                             sleep_model_factory)
from analytics_zoo_tpu.serving.queue_api import (FileBroker,
                                                 InMemoryBroker,
                                                 make_broker)


# --------------------------------------------------------------------------
# broker multi-consumer parity (InMemory / File / Redis)
# --------------------------------------------------------------------------

def _two_consumers(kind, tmp_path):
    """Two consumer handles over ONE stream, fast idle-reclaim, plus a
    cleanup callable."""
    if kind == "memory":
        a = InMemoryBroker(claim_idle_s=0.25, consumer="a")
        return a, a.view(consumer="b"), lambda: None
    if kind == "file":
        root = str(tmp_path / "spool")
        a = FileBroker(root, consumer="a", claim_idle_s=0.25)
        b = FileBroker(root, consumer="b", claim_idle_s=0.25)
        return a, b, lambda: None
    from analytics_zoo_tpu.serving import MiniRedisServer
    srv = MiniRedisServer().start()
    spec = f"redis://{srv.host}:{srv.port}/par?claim_idle_ms=250"
    a, b = make_broker(spec), make_broker(spec)

    def done():
        a.close()
        b.close()
        srv.stop()
    return a, b, done


@pytest.mark.parametrize("kind", ["memory", "file", "redis"])
def test_broker_disjoint_claims(kind, tmp_path):
    a, b, done = _two_consumers(kind, tmp_path)
    try:
        for i in range(6):
            a.enqueue(f"r{i}", b"x")
        ba = a.claim_batch(3, 0.5)
        bb = b.claim_batch(3, 0.5)
        ids_a = {i for i, _ in ba}
        ids_b = {i for i, _ in bb}
        assert ids_a | ids_b == {f"r{i}" for i in range(6)}
        assert not ids_a & ids_b, "two consumers claimed the same entry"
        a.ack_many(sorted(ids_a))
        b.ack_many(sorted(ids_b))
        assert a.pending() == 0
    finally:
        done()


@pytest.mark.parametrize("kind", ["memory", "file", "redis"])
def test_broker_dead_consumer_reclaim(kind, tmp_path):
    """Consumer a claims and dies (never acks); after the idle threshold
    consumer b's next claim steals the pending entries (XAUTOCLAIM
    parity) and counts them in ``reclaimed``."""
    a, b, done = _two_consumers(kind, tmp_path)
    try:
        for i in range(4):
            a.enqueue(f"d{i}", b"y")
        claimed = a.claim_batch(4, 0.5)
        assert len(claimed) == 4
        assert a.pending() == 0         # pending() counts unclaimed only
        time.sleep(0.35)                # a's claim goes idle
        stolen = b.claim_batch(4, 2.0)
        assert {i for i, _ in stolen} == {f"d{i}" for i in range(4)}
        assert b.reclaimed >= 4
        # redelivered entries complete normally through the survivor
        b.put_result("d0", b"ok")
        assert a.get_result("d0", 2.0) == b"ok"
        b.ack_many(["d1", "d2", "d3"])
        assert b.claim_batch(4, 0.4) == []      # nothing left to steal
    finally:
        done()


@pytest.mark.parametrize("kind", ["memory", "file", "redis"])
def test_broker_ack_and_result_release_pending(kind, tmp_path):
    """put_result releases ONE pending entry, ack_many releases all for
    the id — afterwards nothing is left for idle reclaim."""
    a, b, done = _two_consumers(kind, tmp_path)
    try:
        a.enqueue("p0", b"z")
        a.enqueue("p1", b"z")
        got = a.claim_batch(2, 0.5)
        assert len(got) == 2
        a.put_result("p0", b"res")
        a.ack("p1")
        time.sleep(0.35)
        assert b.claim_batch(2, 0.4) == [], \
            "released entries must not be re-delivered"
        assert b.reclaimed == 0
    finally:
        done()


@pytest.mark.parametrize("kind", ["memory", "file", "redis"])
def test_broker_heartbeat_and_oldest_age(kind, tmp_path):
    a, b, done = _two_consumers(kind, tmp_path)
    try:
        assert a.oldest_age_s() == 0.0
        a.enqueue("h0", b"w")
        time.sleep(0.05)
        age = b.oldest_age_s()
        assert age > 0.0
        # claimed-but-unacked entries still age (head-of-line truth)
        a.claim_batch(1, 0.5)
        if kind != "redis":
            # the Redis stream keeps the entry too (XACK only at result),
            # but XRANGE sees it regardless — for the others the claimed
            # store must be included explicitly
            assert b.oldest_age_s() > 0.0
        a.put_result("h0", b"v")
        a.get_result("h0", 1.0)
        assert b.oldest_age_s() == 0.0
        # heartbeats: publish, list within ttl, clear
        a.heartbeat("w0", {"busy_s": 1.25})
        b.heartbeat("w1")
        live = a.live_workers(ttl_s=3.0)
        assert set(live) == {"w0", "w1"}
        assert live["w0"]["busy_s"] == 1.25
        a.clear_heartbeat("w0")
        assert set(b.live_workers(ttl_s=3.0)) == {"w1"}
    finally:
        done()


def test_make_broker_query_params(tmp_path):
    m = make_broker("memory://qp_test?claim_idle_s=0.5")
    assert m.claim_idle_s == 0.5
    f = make_broker(f"file://{tmp_path}/qp?claim_idle_s=0.75")
    assert f.claim_idle_s == 0.75


# --------------------------------------------------------------------------
# autoscaler control loop in isolation (synthetic gauge traces)
# --------------------------------------------------------------------------

def _scaler(**kw):
    kw.setdefault("max_workers", 4)
    kw.setdefault("up_occupancy", 0.75)
    kw.setdefault("down_occupancy", 0.15)
    kw.setdefault("up_sustain_s", 1.0)
    kw.setdefault("down_sustain_s", 2.0)
    kw.setdefault("cooldown_s", 3.0)
    return Autoscaler(**kw)


def test_autoscaler_ramp_scales_up_after_sustain():
    a = _scaler()
    w = 1
    # below threshold: nothing
    assert a.observe(0.0, 0.5, 0, w) == 1
    # saturated but not yet sustained
    assert a.observe(1.0, 0.9, 0, w) == 1
    assert a.observe(1.5, 0.9, 0, w) == 1
    # sustained >= 1.0s -> +1
    w = a.observe(2.1, 0.9, 0, w)
    assert w == 2 and a.scale_ups == 1


def test_autoscaler_spike_is_rejected_by_sustain():
    a = _scaler()
    assert a.observe(0.0, 0.95, 0, 1) == 1
    # dip resets the window; the later spike starts a NEW window
    assert a.observe(0.5, 0.3, 0, 1) == 1
    assert a.observe(1.2, 0.95, 0, 1) == 1
    assert a.observe(1.9, 0.95, 0, 1) == 1     # only 0.7s sustained
    assert a.scale_ups == 0


def test_autoscaler_cooldown_hysteresis_stops_flapping():
    a = _scaler()
    w = 1
    a.observe(0.0, 0.9, 0, w)
    w = a.observe(1.1, 0.9, 0, w)
    assert w == 2
    # still saturated and sustained, but inside cooldown: hold
    a.observe(1.5, 0.9, 0, w)
    w2 = a.observe(3.0, 0.9, 0, w)
    assert w2 == 2 and a.scale_ups == 1
    # sustain evidence kept accumulating through cooldown: the next
    # step lands at the first sample after cooldown expires, not later
    w3 = a.observe(4.2, 0.9, 0, w)
    assert w3 == 3 and a.scale_ups == 2


def test_autoscaler_bounds_never_violated():
    a = _scaler(max_workers=2, cooldown_s=0.0, up_sustain_s=0.1,
                down_sustain_s=0.1)
    w = 1
    for t in range(40):
        w = a.observe(t * 0.5, 0.99, 1000, w)
        assert 1 <= w <= 2
    assert w == 2
    for t in range(40, 120):
        w = a.observe(t * 0.5, 0.0, 0, w)
        assert 1 <= w <= 2
    assert w == 1
    # and never below 1 no matter how long it idles
    for t in range(120, 160):
        assert a.observe(t * 0.5, 0.0, 0, w) == 1


def test_autoscaler_scale_down_needs_sustained_idle_and_empty_queue():
    a = _scaler()
    w = 2
    assert a.observe(0.0, 0.05, 0, w) == 2
    # backlog present: NOT idle even at zero occupancy
    assert a.observe(1.0, 0.05, 10, w) == 2
    assert a.observe(2.0, 0.05, 0, w) == 2      # idle window restarted
    assert a.observe(3.0, 0.05, 0, w) == 2
    w = a.observe(4.1, 0.05, 0, w)
    assert w == 1 and a.scale_downs == 1


def test_autoscaler_queue_depth_triggers_without_occupancy():
    # workers wedged (occupancy flat) but the backlog explodes: depth
    # per worker is the second saturation signal
    a = _scaler(depth_per_worker=8)
    assert a.observe(0.0, 0.0, 100, 2) == 2
    assert a.observe(1.1, 0.0, 100, 2) == 3


# --------------------------------------------------------------------------
# frontend fleet health + queue-age shed (no processes: fake heartbeats)
# --------------------------------------------------------------------------

def test_frontend_fleet_readyz_and_queue_age_shed():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from analytics_zoo_tpu.serving.http_frontend import create_app

    broker = InMemoryBroker(claim_idle_s=30.0)
    app = create_app(broker, timeout_s=2.0, worker_ttl_s=2.0,
                     queue_age_shed_ms=60.0)

    async def run():
        out = {}
        async with TestClient(TestServer(app)) as client:
            # zero live workers -> 503 no_workers
            r = await client.get("/readyz")
            out["no_workers"] = (r.status, (await r.json())["status"])
            broker.heartbeat("w0", {"busy_s": 0.5})
            r = await client.get("/readyz")
            out["ready"] = (r.status, await r.json())
            out["metrics_fleet"] = (await (await client.get(
                "/metrics")).json())["fleet"]
            # stale head-of-line entry -> 429 shed BEFORE enqueue
            broker.enqueue("stale", b"x")
            await asyncio.sleep(0.1)
            depth_before = broker.pending()
            r = await client.post("/predict",
                                  json={"instances": [[1.0, 2.0]]})
            out["shed"] = (r.status, r.headers.get("Retry-After"),
                           await r.json())
            out["depth_unchanged"] = broker.pending() == depth_before
            out["shed_counter"] = (await (await client.get(
                "/metrics")).json())["resilience"]["shed_queue_age"]
            # broker down -> readyz 503 broker_unreachable
            broker.pending = _raise_conn_error
            r = await client.get("/readyz")
            out["broker_down"] = (r.status, (await r.json())["status"])
        return out

    out = asyncio.new_event_loop().run_until_complete(run())
    assert out["no_workers"] == (503, "no_workers")
    assert out["ready"][0] == 200
    assert out["ready"][1]["workers_live"] == 1
    assert out["metrics_fleet"] == {"workers_live": 1, "workers": ["w0"]}
    status, retry_after, body = out["shed"]
    assert status == 429 and retry_after == "1"
    assert body["error"] == "queue too old" and body["queue_age_ms"] > 60
    assert out["depth_unchanged"], "shed must happen BEFORE enqueue"
    assert out["shed_counter"] == 1
    assert out["broker_down"] == (503, "broker_unreachable")


def _raise_conn_error():
    raise ConnectionError("broker down")


def test_frontend_queue_age_shed_disabled_by_default():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from analytics_zoo_tpu.serving.http_frontend import create_app

    broker = InMemoryBroker(claim_idle_s=30.0)
    broker.enqueue("stale", b"x")
    time.sleep(0.05)
    app = create_app(broker, timeout_s=0.2)      # knob default: 0 = off

    async def run():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/predict",
                                  json={"instances": [[1.0]]})
            return r.status

    # no engine consumes the stream: the request times out (answered
    # None) rather than being age-shed — 200 with a null prediction
    assert asyncio.new_event_loop().run_until_complete(run()) == 200


# --------------------------------------------------------------------------
# ServingFleet end-to-end (multi-process, FileBroker — no Redis needed)
# --------------------------------------------------------------------------

def test_sleep_model_is_pickleable_and_scales_by_construction():
    m = sleep_model_factory(k=3.0, batch_ms=1.0)
    assert isinstance(m, SleepModel)
    out = m.predict(np.ones((2, 4), np.float32))
    assert np.allclose(out, 3.0)


def test_fleet_rejects_memory_queue():
    with pytest.raises(ValueError):
        ServingFleet(sleep_model_factory, "memory://nope")


def test_fleet_sigkill_reclaim_and_respawn(tmp_path):
    """The chaos gate, in-tree: two workers over one spool stream, one
    SIGKILLed mid-run. Every request must be answered (the dead
    consumer's pending entries re-deliver to the survivor: reclaimed >
    0, lost == 0) and the supervisor respawns the dead slot."""
    from analytics_zoo_tpu.serving.codecs import decode_payload, \
        encode_payload

    spec = f"file://{tmp_path}/fleet?claim_idle_s=1.0"
    # sleep-bound model slow enough (100ms/batch -> ~40 rps/worker)
    # that the kill lands mid-run while the victim still holds claimed
    # entries in the PEL
    fleet = ServingFleet(
        functools.partial(sleep_model_factory, 2.0, 100.0), spec,
        workers=2, autoscale=False, batch_size=4, max_inflight=8,
        heartbeat_s=0.2, worker_ttl_s=2.0, drain_s=5.0).start()
    broker = make_broker(spec)
    try:
        assert fleet.wait_live(2, 30.0), fleet.metrics()
        n = 48
        for i in range(n):
            broker.enqueue(f"q{i}", encode_payload(
                np.ones(3, np.float32)))
        time.sleep(0.4)         # let both workers fill their inflight
        killed = fleet.kill_worker()
        assert killed is not None
        ok = 0
        for i in range(n):
            raw = broker.get_result(f"q{i}", 20.0)
            assert raw is not None, f"request q{i} silently lost"
            out, meta = decode_payload(raw)
            if not meta.get("error"):
                ok += 1
                assert np.allclose(out, 2.0)
        assert ok == n
        deadline = time.time() + 10.0
        while time.time() < deadline:
            m = fleet.metrics()
            if m["restarts"] >= 1 and m["workers_live"] >= 2:
                break
            time.sleep(0.2)
        assert m["restarts"] >= 1, m
    finally:
        snap = fleet.stop()
    assert snap["reclaimed_total"] > 0, snap
    assert snap["records_out_total"] >= 1


def test_fleet_gives_up_on_workers_that_cannot_start(tmp_path):
    """A worker that exits with an error before its first heartbeat (on a
    TPU host: every chip already belongs to another process) must not be
    respawned forever: MAX_BOOT_FAILURES in a row and the supervisor
    stops, says so, and wait_live stops waiting."""
    from analytics_zoo_tpu.serving.fleet import MAX_BOOT_FAILURES

    # SleepModel(k=float("no-chip")) raises in the child's factory call
    fleet = ServingFleet(
        functools.partial(sleep_model_factory, "no-chip"),
        f"file://{tmp_path}/fleet", workers=3, autoscale=False,
        poll_s=0.05).start()
    try:
        assert not fleet.wait_live(1, 90.0)
        m = fleet.metrics()
        assert m["gave_up"] and m["boot_failures"] == MAX_BOOT_FAILURES, m
        # the three start together; at most the first two deaths respawn
        assert 3 <= m["spawned"] <= 5, m
        assert m["restarts"] == 0 and m["workers_live"] == 0, m
    finally:
        fleet.stop()


def test_fleet_autoscales_up_and_back_down(tmp_path):
    """Occupancy-driven 1 -> 2 -> 1: saturate one worker (sleep-bound, so
    occupancy ~1.0), the control loop adds a worker after the sustain
    window; starve the stream and it retires back to one after the idle
    window + cooldown."""
    from analytics_zoo_tpu.serving.codecs import encode_payload

    spec = f"file://{tmp_path}/auto?claim_idle_s=2.0"
    scaler = Autoscaler(min_workers=1, max_workers=2, up_occupancy=0.6,
                        down_occupancy=0.1, up_sustain_s=0.6,
                        down_sustain_s=1.5, cooldown_s=1.0,
                        depth_per_worker=10_000)
    fleet = ServingFleet(
        functools.partial(sleep_model_factory, 2.0, 40.0), spec,
        workers=1, autoscaler=scaler, batch_size=2, max_inflight=4,
        heartbeat_s=0.15, worker_ttl_s=2.0, poll_s=0.1,
        drain_s=5.0).start()
    broker = make_broker(spec)
    try:
        assert fleet.wait_live(1, 30.0)
        # saturate: ~25 batches of sleep keep occupancy pinned near 1.0
        for i in range(120):
            broker.enqueue(f"a{i}", encode_payload(
                np.ones(2, np.float32), meta={"uri": f"a{i}"}))
        assert fleet.wait_live(2, 30.0), \
            f"never scaled up: {fleet.metrics()}"
        assert fleet.metrics()["scale_ups"] >= 1
        # drain the backlog, then idle -> back down to 1
        deadline = time.time() + 30.0
        while broker.pending() > 0 and time.time() < deadline:
            time.sleep(0.2)
        deadline = time.time() + 25.0
        while time.time() < deadline:
            if fleet.metrics()["scale_downs"] >= 1:
                break
            time.sleep(0.2)
        m = fleet.metrics()
        assert m["scale_downs"] >= 1, m
        assert m["workers_target"] == 1, m
    finally:
        fleet.stop()


def test_fleet_trace_spans_cross_process(tmp_path):
    """One trace id crosses enqueue -> broker -> worker dispatch ->
    respond: the worker process dumps its spans on drain and the parent
    finds its own trace id in them."""
    from analytics_zoo_tpu.obs import trace as _trace
    from analytics_zoo_tpu.serving.codecs import encode_payload

    trace_dir = str(tmp_path / "spans")
    spec = f"file://{tmp_path}/traced?claim_idle_s=2.0"
    fleet = ServingFleet(
        functools.partial(sleep_model_factory, 2.0, 2.0), spec,
        workers=1, autoscale=False, batch_size=4, max_inflight=8,
        heartbeat_s=0.2, worker_ttl_s=2.0, drain_s=5.0,
        worker_env={"ZOO_TRACE": "1"}, trace_dir=trace_dir).start()
    broker = make_broker(spec)
    try:
        assert fleet.wait_live(1, 30.0)
        with _trace.tracing(capacity=256):
            with _trace.span("serving.request"):
                tok = _trace.token()
                trace_id = tok.split(":")[0]
                for i in range(4):
                    broker.enqueue(f"t{i}", encode_payload(
                        np.ones(2, np.float32),
                        meta={"uri": f"t{i}", "trace": tok}))
            for i in range(4):
                assert broker.get_result(f"t{i}", 15.0) is not None
    finally:
        fleet.stop()        # SIGTERM -> drain -> span dump
    files = os.listdir(trace_dir)
    assert files, "worker dumped no span file"
    names_for_trace = set()
    for fn in files:
        with open(os.path.join(trace_dir, fn)) as f:
            for line in f:
                s = json.loads(line)
                if s["trace"] == trace_id:
                    names_for_trace.add(s["name"])
    assert {"serving.dispatch", "serving.respond"} <= names_for_trace, \
        names_for_trace


def test_fleet_cli_entrypoint_registered():
    path = os.path.join(os.path.dirname(__file__), "..",
                        "pyproject.toml")
    with open(path) as f:
        text = f.read()
    assert ('zoo-serving-fleet = '
            '"analytics_zoo_tpu.serving.fleet:main"') in text
