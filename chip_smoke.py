#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the only one that touches JAX, drives the main path through the
entry points a user calls, on a TPU and nowhere else:

  0. device     init_orca_context(cluster_mode="tpu"); refuses any other platform
  1. train      ResNet-50/224, 256 images a chip, uint8 shards on disk ->
                ImageNetPipeline -> TPUEstimator.fit (InfeedPump, transfer
                lanes, sharded_put, on-device prologue), cross-checked against
                the same batches stepped as device-resident arrays; plus the
                in-memory BatchIterator path, whose StagingPool ring is on
                only off the CPU backend
  2. kernel     flash_attention forward and gradient, bf16, S=4096, D=64/128,
                causal and not: Mosaic custom calls in the lowered text and
                agreement with mha_reference in f32; then MultiHeadAttention
  3. serve      SSD-300 ObjectDetector -> ClusterServing over the RESP2
                RedisBroker (in-process MiniRedisServer) behind the HTTP
                frontend; every answer read, none may be an error payload
  4. multichip  with >= 4 devices: stage 1 on dp=4, every device holding its
                shard of the batch and a copy of the parameters, an all-reduce
                in the compiled step, a predict sharded over the four

Each stage prints one JSON line; a stage that fails raises, so the exit code
is non-zero and the last line is never printed. The stage bodies take their
sizes as arguments (tests/test_chip_smoke.py runs them at toy sizes on the CPU
mesh); ``main()`` has no CPU mode. The numbers in the ``observations`` line are
what this run saw, not benchmark metrics.

Usage:  python chip_smoke.py        (on a machine with a TPU)
"""

import asyncio
import functools
import importlib.metadata
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
OUT_DIR = os.path.join(ROOT, "chip_smoke_out")

BF16_EPS = 2.0 ** -8
# tolerances fixed from the dtype before any chip run: largest error over the
# tensor relative to the reference's largest element. bf16 inputs, bf16
# probabilities into the p@v matmul, f32 accumulation: a few ulps forward; the
# backward rounds p and ds to bf16 once more, so twice that (the ratio
# tests/test_attention.py uses between its bf16 forward and gradient bounds)
FWD_TOL = 8 * BF16_EPS
GRAD_TOL = 16 * BF16_EPS


class SmokeFailure(AssertionError):
    """A stage observed something wrong (raised by :func:`check`)."""


# what a stage hands to the next one, not to the reader
_LIVE_OBJECTS = ("ctx", "est", "batch")


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def run_stage(stage: str, fn, **kwargs) -> dict:
    """Run one stage and print its line. A failure prints ``ok: false`` and
    propagates: nothing after it runs and the exit code is non-zero."""
    try:
        observed = fn(**kwargs)
    except BaseException as e:
        print(json.dumps({"stage": stage, "ok": False,
                          "error": f"{type(e).__name__}: {e}"}), flush=True)
        raise
    print(json.dumps({"stage": stage, "ok": True, **{
        k: v for k, v in observed.items() if k not in _LIVE_OBJECTS}},
        default=str), flush=True)
    return observed


# --------------------------------------------------------------------------
# stage 0 — device
# --------------------------------------------------------------------------

def stage_device(cluster_mode: str = "tpu") -> dict:
    import jax
    import jaxlib

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.compile import get_compile_cache
    from analytics_zoo_tpu.native import runtime as native_runtime

    ctx = init_orca_context(cluster_mode=cluster_mode)
    dev = jax.devices()[0]
    native = native_runtime.version()
    check(native != "numpy-fallback",
          "libzoo_runtime.so did not build or load: the host data plane "
          "would run on numpy fallbacks")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    cache_dir = get_compile_cache().cache_dir
    entries = len(os.listdir(cache_dir)) if cache_dir else 0
    return {"ctx": ctx, "platform": dev.platform,
            "device_kind": dev.device_kind, "device_count": len(jax.devices()),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "native_runtime": native,
            "compile_cache_dir": cache_dir,
            "jax_compilation_cache_dir": jax.config.jax_compilation_cache_dir,
            "compile_cache_from_env":
                bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "compile_cache_entries_at_start": entries,
            "mesh": dict(ctx.mesh.shape)}


# --------------------------------------------------------------------------
# stage 1 — train
# --------------------------------------------------------------------------

def _resnet_estimator(depth, num_classes, global_bs, steps_per_epoch, mesh,
                      seed=0):
    from analytics_zoo_tpu.models.image.resnet import resnet
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.optimizers import SGD
    from analytics_zoo_tpu.orca.learn.optimizers.schedule import (
        Poly, SequentialSchedule, Warmup)
    # reference LR recipe (resnet-50-imagenet.py): peak 0.1*global/256,
    # 5-epoch warm-up, poly decay
    peak = 0.1 * global_bs / 256
    warm = 5 * steps_per_epoch
    sched = (SequentialSchedule()
             .add(Warmup(delta=peak / warm), warm)
             .add(Poly(2.0, 85 * steps_per_epoch), 85 * steps_per_epoch))
    return TPUEstimator(
        resnet(depth=depth, num_classes=num_classes),
        loss="sparse_categorical_crossentropy",
        optimizer=SGD(learningrate=0.0, momentum=0.9,
                      leaningrate_schedule=sched),
        mesh=mesh, seed=seed)


def _fit_losses(est, data, tb_dir, **fit_kwargs):
    """``est.fit`` with the per-step losses read back the way a user reads
    them: the estimator's TensorBoard train summary (f32 scalars)."""
    shutil.rmtree(tb_dir, ignore_errors=True)   # a rerun must not read old
    est.set_tensorboard(tb_dir, "smoke")        # events back
    est.fit(data, verbose=False, **fit_kwargs)
    return np.asarray([v for _, v in est.get_train_summary("Loss")],
                      np.float32)


def _resident_losses(est, it, n):
    """Step ``n`` batches of ``it`` through ``engine.train_batch`` as
    device-resident arrays, replaying what ``fit`` does to the iterator
    (one unshuffled sample for the build, then the training epoch) but with
    no pump, no lanes and no staging ring."""
    import jax
    sample = next(it.epoch(shuffle=False, prefetch=False))
    est.engine.build(tuple(np.asarray(a) for a in sample.x))
    losses = []
    gen = it.epoch(prefetch=False)
    for _ in range(n):
        batch = next(gen)
        jax.block_until_ready((batch.x, batch.y))
        losses.append(est.engine.train_batch(batch))
    gen.close()
    return np.asarray(jax.device_get(losses), np.float32)


def _check_resident(params, platform, what):
    import jax
    leaves = jax.tree_util.tree_leaves(params)
    on = {d.platform for leaf in leaves for d in leaf.devices()}
    check(on == {platform}, f"{what} parameters live on {sorted(on)}, "
                            f"not on {platform} devices")
    return len(leaves)


def stage_train(out_dir: str, mesh, platform: str, *, depth: int = 50,
                num_classes: int = 1000, image_size: int = 232,
                crop: int = 224, per_chip_batch: int = 256, steps: int = 8,
                cross_check: int = 4, sync_steps: int = 8,
                ring_rows: int = 2048, ring_features: int = 4096,
                ring_steps: int = 72) -> dict:
    import jax

    from analytics_zoo_tpu.compile import compile_stats
    from analytics_zoo_tpu.orca.data.image import (ImageNetPipeline,
                                                   write_synthetic_imagenet)

    ndev = mesh.devices.size
    global_bs = per_chip_batch * ndev
    data_dir = os.path.join(out_dir, f"imagenet_{ndev}dev")
    shutil.rmtree(data_dir, ignore_errors=True)
    try:
        write_synthetic_imagenet(data_dir, num_images=global_bs * steps,
                                 image_size=image_size,
                                 num_classes=num_classes, seed=0)

        def pipe():
            return ImageNetPipeline(data_dir, batch_size=global_bs,
                                    mesh=mesh, crop_size=crop, train=True,
                                    seed=0)

        # --- the production path, as fit chooses it ----------------------
        est = _resnet_estimator(depth, num_classes, global_bs, steps, mesh)
        t0 = time.perf_counter()
        losses = _fit_losses(
            est, pipe(), os.path.join(out_dir, f"tb_resnet_{ndev}dev"),
            epochs=1, steps_per_epoch=steps)
        jax.block_until_ready(est.engine.params)
        first_fit_s = time.perf_counter() - t0
        check(len(losses) == steps, f"fit logged {len(losses)} losses for "
                                    f"{steps} steps")
        check(bool(np.all(np.isfinite(losses))),
              f"non-finite training loss: {losses.tolist()}")
        n_leaves = _check_resident(est.engine.params, platform, "ResNet")
        pstats = est.data_pipeline_stats()
        check(pstats["h2d_bytes"] > 0, f"no h2d bytes recorded: {pstats}")

        # --- the code tier-1 never ran: staged vs resident ---------------
        ref = _resnet_estimator(depth, num_classes, global_bs, steps, mesh)
        ref_losses = _resident_losses(ref, pipe(), cross_check)
        check(np.array_equal(losses[:cross_check], ref_losses),
              f"pumped fit losses {losses[:cross_check].tolist()} != "
              f"device-resident losses {ref_losses.tolist()} on the same "
              f"{cross_check} batches")
        del ref

        # --- a second epoch on the warm executable: wall time per step,
        # pipeline start-up included (few steps: not a steady-state rate)
        t0 = time.perf_counter()
        est.fit(pipe(), epochs=1, steps_per_epoch=steps, verbose=False)
        jax.block_until_ready(est.engine.params)
        warm_s = (time.perf_counter() - t0) / steps
        batch = next(pipe().epoch(shuffle=False, prefetch=False))
        sync = _sync_probe(est, batch, sync_steps)

        ring = _ring_check(out_dir, mesh, platform, rows=ring_rows,
                           features=ring_features, steps=ring_steps)

        cstats = compile_stats()
        check(cstats["fallbacks"] == 0,
              f"compile plane fell back to plain jit: {cstats['by_label']}")
        return {"est": est, "batch": batch,
                "model": f"resnet{depth}/{crop}",
                "devices": ndev, "global_batch": global_bs, "steps": steps,
                "losses": [round(float(v), 5) for v in losses],
                "cross_check_steps": cross_check,
                "param_leaves_on_device": n_leaves,
                "h2d_bytes": pstats["h2d_bytes"],
                "pipeline": {k: pstats[k] for k in (
                    "assemble_s", "h2d_s", "h2d_MBps", "step_s", "stall_s",
                    "lanes", "depth_peak", "transfer_limited")},
                "first_fit_s": round(first_fit_s, 2),
                "warm_fit_s_per_step": round(warm_s, 4),
                "sync_probe": sync, "ring": ring,
                "compile_fallbacks": cstats["fallbacks"]}
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def _sync_probe(est, batch, n: int) -> dict:
    """Does ``block_until_ready`` wait? Dispatch ``n`` train steps on one
    device-resident batch; time the dispatch loop, the block after it and a
    value fetch after that. If the block waited, the fetch is instant; if
    dispatch is asynchronous, the loop returns long before the block."""
    import jax
    eng = est.engine
    jax.block_until_ready((batch.x, batch.y))
    jax.block_until_ready(eng.train_batch(batch))
    t0 = time.perf_counter()
    for _ in range(n):
        loss = eng.train_batch(batch)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(loss)
    t_block = time.perf_counter() - t0
    float(loss)
    t_fetch = time.perf_counter() - t0
    return {"steps": n, "dispatch_returned_s": round(t_dispatch, 4),
            "block_until_ready_s": round(t_block, 4),
            "fetch_after_block_s": round(t_fetch - t_block, 5)}


def _ring_check(out_dir, mesh, platform, *, rows, features, steps) -> dict:
    """In-memory arrays through ``fit`` -> BatchIterator -> InfeedPump: the
    path whose gathers go into the StagingPool ring wherever the backend is
    not the CPU. More steps than the ring has buffers, so every buffer is
    rewritten while later batches are in flight; a buffer recycled under a
    transfer changes a loss."""
    import flax.linen as nn
    import jax.numpy as jnp

    from analytics_zoo_tpu.native.transfer import staging_enabled
    from analytics_zoo_tpu.orca.learn import utils as learn_utils
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator

    class PixelMLP(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.astype(jnp.float32) * (1.0 / 255.0)
            return nn.Dense(10)(nn.relu(nn.Dense(64)(x)))

    rng = np.random.RandomState(1)
    data = {"x": rng.randint(0, 256, (rows * steps, features), np.uint8),
            "y": rng.randint(0, 10, rows * steps).astype(np.int32)}

    def make():
        return TPUEstimator(PixelMLP(), optimizer="sgd", mesh=mesh,
                            loss="sparse_categorical_crossentropy", seed=0)

    est = make()
    losses = _fit_losses(est, data, os.path.join(out_dir, "tb_ring"),
                         epochs=1, batch_size=rows, steps_per_epoch=steps)
    check(len(losses) == steps and bool(np.all(np.isfinite(losses))),
          f"ring fit: {len(losses)} losses, finite="
          f"{bool(np.all(np.isfinite(losses)))}")
    _check_resident(est.engine.params, platform, "MLP")
    ref = make()
    it = learn_utils.data_to_iterator(data, rows, mesh, shuffle=True)
    ref_losses = _resident_losses(ref, it, steps)
    check(np.array_equal(losses, ref_losses),
          "staged BatchIterator losses differ from device-resident ones at "
          f"steps {np.flatnonzero(losses != ref_losses).tolist()}")
    return {"staging_pool": staging_enabled(), "steps": steps,
            "batch_bytes": rows * features,
            "h2d_bytes": est.data_pipeline_stats()["h2d_bytes"]}


# --------------------------------------------------------------------------
# stage 2 — kernel
# --------------------------------------------------------------------------

def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def stage_kernel(*, seq: int = 4096, head_dims=(64, 128), batch: int = 1,
                 heads: int = 8, layer_seq: int = 2048,
                 layer_hidden: int = 768, layer_heads: int = 12,
                 expect_custom_calls: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.obs.registry import REGISTRY
    from analytics_zoo_tpu.ops import attention as A
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        MultiHeadAttention

    fallthroughs = REGISTRY.counter("zoo_attention_reference_on_tpu_total")
    fell_before = int(fallthroughs.value)
    observed = {}
    for d in head_dims:
        keys = jax.random.split(jax.random.PRNGKey(d), 4)
        q, k, v, w = (jax.random.normal(kk, (batch, seq, heads, d),
                                        jnp.float32) for kk in keys)
        qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
        q32, k32, v32 = (a.astype(jnp.float32) for a in (qb, kb, vb))
        for causal in (False, True):
            def loss(attn, q_, k_, v_):
                # fixed random cotangent w: every output element counts
                out = attn(q_, k_, v_, causal=causal)
                return jnp.sum(out.astype(jnp.float32) * w)

            fwd = jax.jit(functools.partial(A.flash_attention, causal=causal))
            grad = jax.jit(jax.grad(functools.partial(
                loss, A.flash_attention), argnums=(0, 1, 2)))
            n_fwd = fwd.lower(qb, kb, vb).as_text().count("tpu_custom_call")
            n_grad = grad.lower(qb, kb, vb).as_text().count("tpu_custom_call")
            if expect_custom_calls:
                # 1 forward; forward-with-lse + the fused backward for the
                # gradient (a whole dQ fits VMEM at these sizes): neither
                # interpret mode nor mha_reference was taken
                check(n_fwd == 1, f"D={d} causal={causal}: {n_fwd} Mosaic "
                                  "custom calls in the forward, want 1")
                check(n_grad == 2, f"D={d} causal={causal}: {n_grad} Mosaic "
                                   "custom calls in the gradient, want 2")
            out = fwd(qb, kb, vb)
            grads = grad(qb, kb, vb)
            with jax.default_matmul_precision("highest"):
                out_ref = jax.jit(functools.partial(
                    A.mha_reference, causal=causal))(q32, k32, v32)
                grads_ref = jax.jit(jax.grad(functools.partial(
                    loss, A.mha_reference), argnums=(0, 1, 2)))(q32, k32, v32)
            check(out.dtype == jnp.bfloat16 and out.shape == qb.shape,
                  f"flash output {out.dtype}{out.shape}")
            errs = {"out": _rel_err(out, out_ref)}
            for name, g, gr in zip(("dq", "dk", "dv"), grads, grads_ref):
                errs[name] = _rel_err(g, gr)
            check(all(np.isfinite(e) for e in errs.values()),
                  f"D={d} causal={causal}: non-finite error {errs}")
            check(errs["out"] <= FWD_TOL,
                  f"D={d} causal={causal}: forward off mha_reference by "
                  f"{errs['out']:.4g} > {FWD_TOL:.4g}")
            worst = max(errs[n] for n in ("dq", "dk", "dv"))
            check(worst <= GRAD_TOL,
                  f"D={d} causal={causal}: gradient off mha_reference by "
                  f"{errs} > {GRAD_TOL:.4g}")
            observed[f"d{d}_{'causal' if causal else 'full'}"] = {
                "custom_calls": [n_fwd, n_grad],
                **{n: round(e, 5) for n, e in errs.items()}}

    # the way BERT / TransformerLayer reach the kernel
    x = jax.random.normal(jax.random.PRNGKey(7),
                          (2, layer_seq, layer_hidden), jnp.bfloat16)
    flash = MultiHeadAttention(n_head=layer_heads, hidden_size=layer_hidden,
                               causal=True, strategy="flash")
    full = MultiHeadAttention(n_head=layer_heads, hidden_size=layer_hidden,
                              causal=True, strategy="full")
    variables = flash.init(jax.random.PRNGKey(8), x)
    apply_flash = jax.jit(flash.apply)
    n_layer = apply_flash.lower(variables, x).as_text().count(
        "tpu_custom_call")
    if expect_custom_calls:
        check(n_layer == 1, f"MultiHeadAttention lowered {n_layer} Mosaic "
                            "custom calls, want 1")
    y = apply_flash(variables, x)
    y_ref = jax.jit(full.apply)(variables, x)
    layer_err = _rel_err(y, y_ref)
    check(np.isfinite(layer_err) and layer_err <= FWD_TOL,
          f"MultiHeadAttention flash vs full: {layer_err:.4g} > "
          f"{FWD_TOL:.4g}")
    observed["layer"] = {"custom_calls": n_layer, "err": round(layer_err, 5)}
    fell = int(fallthroughs.value) - fell_before
    check(fell == 0, f"{fell} flash_attention call site(s) fell through to "
                     "mha_reference on the TPU")
    return {"seq": seq, "dtype": "bfloat16", "fwd_tol": FWD_TOL,
            "grad_tol": GRAD_TOL, "reference_fallthroughs": fell, **observed}


# --------------------------------------------------------------------------
# stage 3 — serve
# --------------------------------------------------------------------------

def stage_serve(*, model_type: str = "ssd300", image_size: int = 300,
                batch_size: int = 8, n_single: int = 8, n_burst: int = 2,
                max_detections: int = 100, timeout_s: float = 120.0) -> dict:
    from aiohttp import ClientSession, ClientTimeout, web

    from analytics_zoo_tpu.compile import compile_stats
    from analytics_zoo_tpu.models.image.objectdetection import ObjectDetector
    from analytics_zoo_tpu.serving import (ClusterServing, MiniRedisServer,
                                           RedisBroker)
    from analytics_zoo_tpu.serving.http_frontend import create_app

    before = compile_stats()["by_label"].get("serving", {})
    det = ObjectDetector(model_type=model_type, image_size=image_size)
    det.compile()
    model = det.as_inference_model(max_detections=max_detections)
    n_classes = len(det.class_names) + 1
    rng = np.random.RandomState(3)
    n_req = n_single + n_burst * batch_size
    # integer pixel values: short JSON, exact round trip into f32
    pixels = rng.randint(0, 256, (n_req, image_size, image_size, 3))
    imgs = pixels.astype(np.float32)

    srv = MiniRedisServer(port=0).start()
    serving = None
    try:
        broker = RedisBroker("127.0.0.1", srv.port, stream="chip-smoke")
        t0 = time.perf_counter()
        serving = ClusterServing(model, queue=broker, batch_size=batch_size,
                                 batch_timeout_ms=20).start(example=imgs[:1])
        precompile_s = time.perf_counter() - t0

        async def drive():
            app = create_app(
                queue=RedisBroker("127.0.0.1", srv.port, stream="chip-smoke"),
                serving=serving, timeout_s=timeout_s)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            url = f"http://127.0.0.1:{port}"
            try:
                async with ClientSession(
                        timeout=ClientTimeout(total=timeout_s + 30)) as sess:
                    async def post(lo, hi):
                        async with sess.post(f"{url}/predict", json={
                                "instances": [{"image": im.tolist()}
                                              for im in pixels[lo:hi]]
                                }) as resp:
                            check(resp.status == 200,
                                  f"/predict answered {resp.status}: "
                                  f"{await resp.text()}")
                            return (await resp.json())["predictions"]
                    answers = []
                    # one record at a time: the smallest bucket
                    for i in range(n_single):
                        answers += await post(i, i + 1)
                    # whole batches at once: the largest bucket
                    bursts = await asyncio.gather(*[
                        post(n_single + j * batch_size,
                             n_single + (j + 1) * batch_size)
                        for j in range(n_burst)])
                    for b in bursts:
                        answers += b
                    async with sess.get(f"{url}/metrics") as resp:
                        metrics = await resp.json()
                    return answers, metrics
            finally:
                await runner.cleanup()

        answers, metrics = asyncio.run(drive())
        check(len(answers) == n_req,
              f"{len(answers)} answers for {n_req} requests")
        n_found = 0
        for i, ans in enumerate(answers):
            # the engine answers a failed batch with {"error": ...} and
            # keeps serving, by design — so every answer is read
            check(not isinstance(ans, dict),
                  f"request {i} answered an error payload: {ans}")
            dets = np.asarray(ans, np.float32)
            check(dets.shape == (max_detections, 6),
                  f"request {i}: detection payload shape {dets.shape}")
            check(bool(np.all(np.isfinite(dets))),
                  f"request {i}: non-finite detections")
            labels, scores = dets[:, 0], dets[:, 1]
            check(bool(np.all((labels == -1) | ((labels >= 1)
                                                & (labels < n_classes)))),
                  f"request {i}: labels outside -1/1..{n_classes - 1}")
            check(bool(np.all((scores >= 0) & (scores <= 1))),
                  f"request {i}: scores outside [0, 1]")
            n_found += int(np.sum(labels >= 1))
        # the wire delivers what the model computes: a record served alone
        # came through the same one-record executable a direct predict uses
        direct = np.asarray(model.predict(imgs[:1]))[0]
        check(np.array_equal(np.asarray(answers[0], np.float32), direct),
              "request 0 over HTTP+Redis differs from InferenceModel.predict "
              "on the same record")
        res = metrics["resilience"]
        check(res["batch_failures"] == 0 and res["decode_errors"] == 0,
              f"/metrics reports failures: {res}")
        check(metrics["records_out"] >= n_req,
              f"/metrics records_out {metrics['records_out']} < {n_req}")
        after = compile_stats()["by_label"].get("serving", {})
        programs = sum(after.get(f, 0) - before.get(f, 0)
                       for f in ("compiles", "disk_hits", "cache_hits"))
        check(programs >= 2, f"{programs} serving program(s) resolved, want "
                             ">= 2 shape buckets")
        check(compile_stats()["fallbacks"] == 0,
              "compile plane fell back to plain jit while serving")
    finally:
        if serving is not None:
            serving.stop()
        srv.stop()
    return {"model": f"{model_type}/{image_size}", "requests": n_req,
            "answers_ok": len(answers), "detections": n_found,
            "devices": metrics["devices"], "buckets": list(model.buckets[:4]),
            "serving_programs": programs,
            "precompile_s": round(precompile_s, 2),
            "inference_ms": metrics["stages"].get("inference", {}),
            "batch_failures": res["batch_failures"]}


# --------------------------------------------------------------------------
# stage 4 — multichip
# --------------------------------------------------------------------------

def stage_multichip(out_dir: str, mesh, platform: str, *, steps: int = 4,
                    per_chip_batch: int = 256, **train_sizes) -> dict:
    import jax

    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel

    devices = list(mesh.devices.flat)
    ndev = len(devices)
    trained = stage_train(out_dir, mesh, platform, steps=steps,
                          cross_check=min(4, steps),
                          per_chip_batch=per_chip_batch, **train_sizes)
    est, batch = trained["est"], trained["batch"]
    eng = est.engine

    for leaf in jax.tree_util.tree_leaves(eng.params):
        check(leaf.sharding.is_fully_replicated
              and set(leaf.devices()) == set(devices),
              f"a parameter is not replicated over all {ndev} devices: "
              f"{leaf.sharding}")

    shards = batch.x[0].addressable_shards
    check({s.device for s in shards} == set(devices)
          and all(s.data.shape[0] == per_chip_batch for s in shards),
          f"batch shards: {[(s.device.id, s.data.shape) for s in shards]}")

    in_use = {}
    for d in devices:
        stats = d.memory_stats()     # None on the CPU backend only
        if stats is None and platform == "cpu":
            continue
        check(stats is not None and stats.get("bytes_in_use", 0) > 0,
              f"device {d.id} reports no memory in use: {stats}")
        in_use[d.id] = stats["bytes_in_use"]

    hlo = eng.ensure_jit_train().lower(
        *eng.train_step_args(batch)).compile().as_text()
    n_allreduce = hlo.count("all-reduce")
    check(n_allreduce > 0, "no all-reduce in the compiled dp train step")

    # the default InferenceModel: "every local device" is its claim
    model = InferenceModel().load_jax(
        est.module, {"params": eng.params, **eng.extra_vars})
    images = np.asarray(batch.x[0][:2 * ndev])
    out = model._predict_device([images], len(images))
    check({s.device for s in out.addressable_shards} == set(devices),
          f"predict output lives on {sorted(d.id for d in out.devices())}, "
          f"not on all {ndev} devices")
    logits = model.predict(images)
    check(logits.shape[0] == len(images)
          and bool(np.all(np.isfinite(logits))),
          f"predict returned {logits.shape}, finite="
          f"{bool(np.all(np.isfinite(logits)))}")
    return {**trained, "params_replicated_on": ndev,
            "batch_shard_rows": per_chip_batch, "bytes_in_use": in_use,
            "all_reduce_in_step": n_allreduce,
            "predict_sharded_over": ndev}


# --------------------------------------------------------------------------
# observations
# --------------------------------------------------------------------------

def h2d_probe(nbytes: int = 64 << 20) -> dict:
    """One host->device copy: when ``device_put`` returns and when the
    array is ready. Two different times mean the put is asynchronous and a
    timer around the bare call measures the enqueue."""
    import jax
    rng = np.random.RandomState(5)
    warm = rng.randint(0, 256, nbytes, np.uint8)
    jax.block_until_ready(jax.device_put(warm))
    a = rng.randint(0, 256, nbytes, np.uint8)
    t0 = time.perf_counter()
    dev = jax.device_put(a)
    t_put = time.perf_counter() - t0
    jax.block_until_ready(dev)
    t_ready = time.perf_counter() - t0
    return {"mb": nbytes >> 20, "put_returned_s": round(t_put, 5),
            "ready_s": round(t_ready, 5),
            "MBps": round(nbytes / t_ready / 1e6, 1)}


def main() -> int:
    t_start = time.perf_counter()
    os.makedirs(OUT_DIR, exist_ok=True)

    dev = run_stage("device", stage_device)
    ctx, platform = dev["ctx"], dev["platform"]

    import jax

    from analytics_zoo_tpu.compile import compile_stats
    from analytics_zoo_tpu.parallel.mesh import create_mesh

    one_chip = create_mesh({"dp": 1}, devices=ctx.devices[:1])
    train = run_stage("train", stage_train, out_dir=OUT_DIR, mesh=one_chip,
                      platform=platform)
    del train["est"], train["batch"]
    run_stage("kernel", stage_kernel)
    run_stage("serve", stage_serve)
    if jax.device_count() >= 4:
        run_stage("multichip", stage_multichip, out_dir=OUT_DIR,
                  mesh=ctx.mesh, platform=platform)
    else:
        print(json.dumps({"stage": "multichip", "ok": True, "skipped":
                          f"skipped ({jax.device_count()} device)"}),
              flush=True)

    stats = compile_stats()
    print(json.dumps({"observations": {
        "compile_s_by_label": {
            lbl: {"compiles": b["compiles"], "disk_hits": b["disk_hits"],
                  "compile_s": round(b["compile_s"], 2)}
            for lbl, b in stats["by_label"].items()},
        "warm_fit_s_per_step": train["warm_fit_s_per_step"],
        "sync_probe": train["sync_probe"],
        "pump": train["pipeline"],
        "h2d_probe": h2d_probe(),
        "wall_s": round(time.perf_counter() - t_start, 1)}}), flush=True)

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
