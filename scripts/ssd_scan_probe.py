#!/usr/bin/env python
"""The Mamba-2 scan alone on the chip, at both hybrid cells' shapes:
``_ssd_chunked`` (jax.numpy, differentiated by JAX) against the Pallas
kernels (``_ssd_kernels``).

    python scripts/ssd_scan_probe.py [--shapes granite,nemotron]
        [--blocks 8,16] [--iters 5] [--out report.json]

For each shape (one 8192-token sequence; Granite: 64 heads of 64, state 128,
one group, chunk 256; Nemotron: 16 heads, chunk 128): device milliseconds
of the forward and of the forward and backward (``jax.grad`` of a weighted
sum of y, all six gradients), every operation of the call and the custom
calls among them, from a profiler trace of ``--iters`` calls (x and its
gradient enter and leave as (batch, seq, heads * head_dim), as they lie in
the model, so no relayout of them is timed); each one's share of the
mixer's least time (``harness/work_granite.py`` / ``work_hybrid.py``
``scan_min_seconds`` over the configuration's Mamba-2 blocks); a step's
time under ``ssm.scan`` as the cell runs it (each mixer's forward twice,
the remat's, and its backward once) and that step's share; and each side's
largest gap to the float32 reference (the chunked form on float32 inputs
at the highest precision) on the same inputs, output and gradients, over
the reference's largest magnitude. ``--blocks`` times the kernels at other
heads-per-block too (the chosen one is ``_heads_per_block``'s). Prints a
line a side and shape; ``--out`` also writes the whole report as JSON.
Refuses any platform but ``tpu``: a time comes from the chip.
"""

import argparse
import functools
import glob
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

SHAPES = {
    "granite": ("granite4_h_micro_vp8.json", "work_granite", "mamba"),
    "nemotron": ("nemotron3_super_tp8_ep64.json", "work_hybrid", "M"),
}


def inputs(cfg: dict, seq: int, seed: int):
    """x, dt, A, B, C, D of one mixer as the model makes them: bf16 x, B
    and C, dt = softplus(. + dt_bias) and A = -exp(A_log) in float32."""
    import jax
    import jax.numpy as jnp
    h, p = cfg["heads"], cfg["head_dim"]
    g, n = cfg["groups"], cfg["state"]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    dt_bias = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
        k[0], (h,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))))
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, seq, h)) + dt_bias)
    a = -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0,
                                    maxval=jnp.log(16.0)))
    return (jax.random.normal(k[3], (1, seq, h * p), bf), dt, a,
            jax.random.normal(k[4], (1, seq, g, n), bf),
            jax.random.normal(k[5], (1, seq, g, n), bf),
            jnp.ones((h,), jnp.float32))


def device_ms(fn, args, iters: int):
    """Device ms a call of ``fn``: every operation on the device's ``XLA
    Ops`` line, and of them the custom calls (the kernels)."""
    import jax
    jax.block_until_ready(fn(*args))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    every = kernels = 0.0
    for f in glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb")):
        for plane in jax.profiler.ProfileData.from_file(f).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    every += ev.duration_ns / 1e6
                    if "custom-call" in ev.name or "pallas" in ev.name:
                        kernels += ev.duration_ns / 1e6
    return every / iters, kernels / iters


def worst_gap(got, want) -> float:
    import jax.numpy as jnp
    want = want.astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(want)))
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / scale


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="granite,nemotron")
    ap.add_argument("--blocks", default="",
                    help="other heads-per-block to time the kernels at")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2_147_483_659)
    ap.add_argument("--out", help="write the report here as JSON")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import ssm
    from harness import spec
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"ssd_scan_probe: platform {dev.platform!r}; no result",
              file=sys.stderr)
        return 3
    peaks = spec.load_json(os.path.join(REPO, "benchmarks", "harness",
                                        "peaks.json"))["by_device_kind"][
                                            dev.device_kind]
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "args": vars(args)}
    seq = 8192
    for name in args.shapes.split(","):
        cfg_file, work_name, kind = SHAPES[name]
        cfg = spec.load_json(os.path.join(REPO, "benchmarks", "configs",
                                          cfg_file))
        cfg = spec.load_py(os.path.join(REPO, "benchmarks", cfg[
            "factory"])).model_config(cfg)         # the layers' view
        work = __import__(f"harness.{work_name}", fromlist=["x"])
        z = work.sizes(cfg)
        mixers = work.blocks(cfg)[kind]
        shape = {"heads": z["m_heads"], "head_dim": z["m_dim"],
                 "groups": z["groups"], "state": z["state"],
                 "chunk": z["chunk"]}
        least = work.scan_min_seconds(cfg, seq, 1, 2, peaks) / mixers * 1e3
        xs = inputs(shape, seq, args.seed)
        chunk = shape["chunk"]
        w = jax.random.normal(jax.random.PRNGKey(1), xs[0].shape, jnp.float32)
        ref_in = tuple(t.astype(jnp.float32) for t in xs)
        heads4 = (1, seq, shape["heads"], shape["head_dim"])

        def flat(scan):                 # x and y as (1, seq, heads * dim)
            return lambda x, *a: scan(x.reshape(heads4), *a).reshape(
                x.shape)

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

        # the float32 reference: the chunked form at the highest precision
        # (the recurrence's equal in the tier-1 tests; the recurrence's own
        # gradient would keep 8192 states)
        ref = flat(functools.partial(ssm._ssd_chunked, chunk=chunk))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*ref_in)
            want_g = jax.jit(jax.grad(loss(ref), argnums=range(6)))(*ref_in)
        hb = ssm._heads_per_block(shape["heads"],
                                  shape["heads"] // shape["groups"], chunk,
                                  seq, shape["head_dim"], shape["state"], 2)
        sides = {"chunked": (flat(functools.partial(ssm._ssd_chunked,
                                                     chunk=chunk)), None),
                 "kernels": (flat(functools.partial(ssm._ssd_kernels,
                                                    chunk=chunk)), hb)}
        rec = {"shape": shape, "mixers": mixers,
               "least_ms_a_mixer": round(least, 4)}
        for side, (fn, heads) in sides.items():
            fwd, grad = jax.jit(fn), jax.jit(jax.grad(loss(fn),
                                                      argnums=range(6)))
            f_ms, f_kern = device_ms(fwd, xs, args.iters)
            g_ms, g_kern = device_ms(grad, xs, args.iters)
            step_ms = mixers * (f_ms + g_ms)
            rec[side] = {
                "heads_per_block": heads,
                "fwd_ms": round(f_ms, 4), "fwd_bwd_ms": round(g_ms, 4),
                "fwd_kernels_ms": round(f_kern, 4),
                "fwd_bwd_kernels_ms": round(g_kern, 4),
                "share_of_least_pct": round(100 * least / g_ms, 2),
                "step_scan_ms": round(step_ms, 3),
                "step_share_of_least_pct": round(
                    100 * least * mixers / step_ms, 2),
                "gap_y": worst_gap(fwd(*xs), want),
                "gap_grads": [worst_gap(gg, ww) for gg, ww in zip(
                    grad(*xs), want_g)]}
            print(name, side, json.dumps(rec[side]), flush=True)
        for heads in [int(b) for b in args.blocks.split(",") if b]:
            if heads == hb:
                continue
            fwd = jax.jit(flat(lambda *a, heads=heads: ssm._scan_forward(
                *a, chunk, heads, False, False)[0]))

            def fb(x, *a, heads=heads):
                x = x.reshape(heads4)
                y, states = ssm._scan_forward(x, *a, chunk, heads, True, False)
                return ssm._scan_backward(x, *a, states, y, chunk, heads,
                                          False)
            try:
                rec[f"kernels_{heads}"] = {
                    "fwd_ms": round(device_ms(fwd, xs, args.iters)[0], 4),
                    "fwd_bwd_ms": round(device_ms(jax.jit(fb), xs,
                                                  args.iters)[0], 4)}
            except Exception as e:                      # noqa: BLE001
                rec[f"kernels_{heads}"] = {"error": str(e).splitlines()[0]}
            print(name, heads, json.dumps(rec[f"kernels_{heads}"]),
                  flush=True)
        report[name] = rec
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
