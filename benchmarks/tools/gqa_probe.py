#!/usr/bin/env python3
"""The flash kernels alone on the chip at the grouped-query cell's shapes
(1 x 16384 positions, 32 query heads on 4 kv heads of 128, bfloat16): device
ms a launch of each custom call from a profiler trace, global and windowed,
at a few forward tile sizes; and a bfloat16 check of output and gradients
against ``mha_reference`` in float32 at shapes that take the fused backward
and the two-kernel one.

    chiprun -- python benchmarks/tools/gqa_probe.py chiprun_out/gqa_probe.json
"""

import glob
import json
import os
import sys
import tempfile
import time

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(TOOLS)))

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402

import analytics_zoo_tpu.ops.attention as A              # noqa: E402

TINY = bool(os.environ.get("PROBE_TINY"))
d0 = jax.devices()[0]
out = {"device": {"platform": d0.platform, "kind": d0.device_kind}}
key = jax.random.PRNGKey(0)


def qkv(s, h, hk, d=128, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (1, s, h, d), dtype),
            jax.random.normal(ks[1], (1, s, hk, d), dtype),
            jax.random.normal(ks[2], (1, s, hk, d), dtype))


def kernel_times(fn, *args, n=3):
    """Device ms a launch of each custom call, from a profiler trace."""
    d = tempfile.mkdtemp()
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    wall = time.perf_counter() - t0
    jax.profiler.start_trace(d)
    for _ in range(n):
        o = fn(*args)
    jax.block_until_ready(o)
    jax.profiler.stop_trace()
    res = {}
    for f in glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb")):
        pd = jax.profiler.ProfileData.from_file(f)
        for plane in pd.planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    if "custom-call" in ev.name or "flash" in ev.name:
                        res.setdefault(ev.name[:48], []).append(
                            ev.duration_ns / 1e6)
    return {"wall_ms": wall * 1e3,
            "kernels_ms": {k: [round(sum(v) / len(v), 3), len(v)]
                           for k, v in res.items()}}


S, H, HK = (256, 8, 2) if TINY else (16384, 32, 4)
W = 64 if TINY else 2048
q, k, v = qkv(S, H, HK)
for name, window, blocks in (("global", None, [(1024, 1024)]),
                             ("window", W, [(1024, 1024), (512, 1024),
                                            (512, 512), (1024, 512),
                                            (256, 512)])):
    for bq, bk in blocks:
        if TINY:
            bq, bk = bq // 16, bk // 16
        both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(A.flash_attention(
            q, k, v, causal=True, window=window, block_q=bq, block_k=bk
        ).astype(jnp.float32)), (0, 1, 2)))
        rec = kernel_times(both, q, k, v)
        out[f"{name}_{bq}x{bk}"] = rec
        print(name, bq, bk, rec, flush=True)

# correctness in bf16 against the float32 reference: grouped, windowed,
# the fused backward (a kv head's dQ inside the budget) and the pair
for s, window in ((2048, 640), (2048, None), (1024, 300)):
    if TINY:
        s, window = s // 8, window and window // 8
    q, k, v = qkv(s, 8, 2)
    w = jax.random.normal(key, (1, s, 8, 128), jnp.float32)

    def loss(f):
        return lambda q, k, v: jnp.sum(
            f(q, k, v, causal=True, window=window).astype(jnp.float32) * w)
    rec = {}
    for path, budget in (("fused", A._FUSED_BWD_DQ_BYTES), ("pair", 0)):
        keep, A._FUSED_BWD_DQ_BYTES = A._FUSED_BWD_DQ_BYTES, budget
        g = jax.jit(jax.grad(loss(A.flash_attention), (0, 1, 2)))(q, k, v)
        A._FUSED_BWD_DQ_BYTES = keep
        rec[path] = g
    with jax.default_matmul_precision("highest"):
        gr = jax.jit(jax.grad(loss(A.mha_reference), (0, 1, 2)))(
            *(a.astype(jnp.float32) for a in (q, k, v)))
    line = {"grad_rel_err": [float(jnp.abs(a.astype(jnp.float32) - r).max()
                                   / jnp.abs(r).max())
                             for a, r in zip(rec["fused"], gr)],
            "fused_equals_pair": [bool(jnp.all(a == c)) for a, c in
                                  zip(rec["fused"], rec["pair"])],
            "paths": [A._BACKWARD_FUSED.value, A._BACKWARD_TWO_KERNEL.value],
            "tiles": [A._TILES_VISITED.value, A._TILES_NEEDED.value]}
    out[f"check_{s}_{window}"] = line
    print("check", s, window, line, flush=True)
os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
json.dump(out, open(sys.argv[1], "w"), indent=1)
