#!/usr/bin/env python
"""Who gets the chips when several processes on one TPU host ask for them?

A chip belongs to one process at a time, and both fleets
(serving/fleet.py, streaming/fleet.py) start one process per worker, each of
which takes ``jax.local_devices()`` — all of them. This probe establishes, on
a multi-chip host, what that means; the parent never touches JAX.

  plain     N processes, inherited environment, each runs one jitted matmul
  pinned    N processes, process k given chip k through the environment
            (pinned_env) — the per-worker assignment R8 needs
  fleet     a ServingFleet of N workers serving a jitted InferenceModel,
            workers started as the fleet starts them today

Prints one JSON line per leg. Run it through the chip tool on a four-chip
host: ``python scripts/fleet_chip_probe.py 4``.
"""

import json
import multiprocessing as mp
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pinned_env(k: int) -> dict:
    """Environment that gives one process chip ``k`` of the host and nothing
    else (what jax's own multi-process TPU tests set, minus the slice
    topology: these are independent one-chip replicas)."""
    return {"TPU_VISIBLE_CHIPS": str(k),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


def _matmul_child(k: int, env: dict, out):
    os.environ.update(env)
    t0 = time.perf_counter()
    try:
        import jax
        import jax.numpy as jnp
        devs = jax.devices()
        x = jnp.ones((2048, 2048), jnp.bfloat16)
        y = float(jax.jit(lambda a: (a @ a).astype(jnp.float32).sum())(x))
        out.put({"proc": k, "ok": True, "platform": devs[0].platform,
                 "device_ids": [d.id for d in devs], "sum": y,
                 "s": round(time.perf_counter() - t0, 1)})
    except BaseException as e:    # noqa: BLE001 — the finding IS the error
        out.put({"proc": k, "ok": False,
                 "error": f"{type(e).__name__}: {str(e)[:300]}",
                 "s": round(time.perf_counter() - t0, 1)})
        raise


def _run_procs(n: int, env_for, timeout_s: float = 180.0) -> list:
    ctx = mp.get_context("spawn")
    out = ctx.Queue()
    procs = [ctx.Process(target=_matmul_child, args=(k, env_for(k), out))
             for k in range(n)]
    for p in procs:
        p.start()
    results = []
    deadline = time.time() + timeout_s
    while len(results) < n and time.time() < deadline:
        try:
            results.append(out.get(timeout=1.0))
        except Exception:   # noqa: BLE001 — queue.Empty: keep waiting
            if not any(p.is_alive() for p in procs) and out.empty():
                break
    reported = {r["proc"] for r in results}
    for k, p in enumerate(procs):
        p.join(timeout=60)      # the TPU runtime takes its time to shut down
        if p.is_alive():
            p.kill()
            p.join(timeout=5)
        if k not in reported:
            results.append({"proc": k, "ok": False,
                            "error": "no report (hung or killed)"})
    for r in results:
        r.setdefault("exitcode", procs[r["proc"]].exitcode)
    return sorted(results, key=lambda r: r["proc"])


def jitted_model_factory():
    """A small jitted InferenceModel: what a real fleet worker builds."""
    import flax.linen as nn
    import jax
    import numpy as np

    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(8)(nn.relu(nn.Dense(256)(x)))

    net = Net()
    variables = net.init(jax.random.PRNGKey(0), np.zeros((1, 64), np.float32))
    model = InferenceModel().load_jax(net, variables)
    model.predict(np.zeros((4, 64), np.float32))     # compile on the chip
    return model


def _fleet_leg(n: int, wait_s: float = 120.0) -> dict:
    import tempfile

    from analytics_zoo_tpu.serving.fleet import ServingFleet
    root = tempfile.mkdtemp(prefix="zoo-chip-probe-")
    fleet = ServingFleet(jitted_model_factory, f"file://{root}/q", workers=n,
                         autoscale=False, heartbeat_s=0.5, worker_ttl_s=5.0,
                         poll_s=0.25).start()
    t0 = time.time()
    try:
        all_live = fleet.wait_live(n, wait_s)
        m = fleet.metrics()
    finally:
        fleet.stop()
    return {"workers_wanted": n, "all_live": all_live,
            "workers_live": m["workers_live"], "spawned": m["spawned"],
            "restarts": m["restarts"], "boot_failures": m["boot_failures"],
            "gave_up": m["gave_up"], "waited_s": round(time.time() - t0, 1)}


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    print(json.dumps({"leg": "plain",
                      "procs": _run_procs(n, lambda k: {})}), flush=True)
    print(json.dumps({"leg": "pinned", "env": pinned_env(0),
                      "procs": _run_procs(n, pinned_env)}), flush=True)
    print(json.dumps({"leg": "fleet", **_fleet_leg(n)}), flush=True)


if __name__ == "__main__":
    main()
