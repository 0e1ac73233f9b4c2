#!/usr/bin/env python3
"""Kernel timings on the chip that the decoder model's choices rest on: the
flash kernels at MLA's head sizes (192 for q.k, 128 for v) beside v padded to
192 and the equal-size 128 case, and the grouped products of the held experts
through megablox's Pallas kernel and through ``lax.ragged_dot``.

    chiprun -- python benchmarks/tools/lm_probe.py --out chiprun_out/lm_probe.json
"""

import argparse
import json
import os
import sys
import time

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(TOOLS)))


def timed(fn, *args, n=5):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from analytics_zoo_tpu.ops.attention import flash_attention, mha_reference
    from analytics_zoo_tpu.parallel.expert_parallel import grouped_matmul
    d = jax.devices()[0]
    out = {"device": {"platform": d.platform, "kind": d.device_kind}}
    key = jax.random.PRNGKey(0)
    b, s, h = 2, 8192, 32

    def qkv(dq, dv, seq=s, dtype=jnp.bfloat16):
        ks = jax.random.split(key, 3)
        return (jax.random.normal(ks[0], (b, seq, h, dq), dtype),
                jax.random.normal(ks[1], (b, seq, h, dq), dtype),
                jax.random.normal(ks[2], (b, seq, h, dv), dtype))

    for name, dq, dv in (("mla_192_128", 192, 128), ("padded_192_192", 192,
                                                     192),
                         ("equal_128_128", 128, 128)):
        q, k, v = qkv(dq, dv)
        fwd = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, sm_scale=192 ** -0.5))
        both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, sm_scale=192 ** -0.5).astype(jnp.float32)),
            (0, 1, 2)))
        tf, tb = timed(fwd, q, k, v), timed(both, q, k, v)
        flops = b * s * s / 2 * 2 * h * (dq + dv)
        out[f"flash_{name}"] = {
            "fwd_ms": tf * 1e3, "fwd_bwd_ms": tb * 1e3,
            "fwd_tflops": flops / tf / 1e12,
            "fwd_bwd_tflops": 3 * flops / tb / 1e12}
        print(name, out[f"flash_{name}"], flush=True)
    q, k, v = qkv(192, 128, seq=1024)
    got = flash_attention(q, k, v, causal=True).astype(jnp.float32)
    want = mha_reference(*(a.astype(jnp.float32) for a in (q, k, v)),
                         causal=True)
    out["flash_vs_reference_max_abs"] = float(jnp.abs(got - want).max())
    print("flash vs reference", out["flash_vs_reference_max_abs"], flush=True)

    m, kk, n, g = 16384, 2048, 768, 16
    rng = np.random.RandomState(0)
    for rows in (8192, 16384):
        per = rng.multinomial(rows, np.ones(g) / g)
        sizes = jnp.asarray(list(per) + [m - rows], jnp.int32)
        lhs = jax.random.normal(key, (m, kk), jnp.bfloat16)
        rhs = jax.random.normal(key, (g, kk, n), jnp.bfloat16) * 0.02
        for impl in ("pallas", "ragged_dot"):
            f = jax.jit(lambda l, r, impl=impl: grouped_matmul(
                l, r, sizes, impl=impl))
            gr = jax.jit(jax.grad(lambda l, r, impl=impl: jnp.sum(
                grouped_matmul(l, r, sizes, impl=impl).astype(jnp.float32)),
                (0, 1)))
            tf, tb = timed(f, lhs, rhs), timed(gr, lhs, rhs)
            flops = 2.0 * rows * kk * n
            out[f"gmm_{impl}_rows{rows}"] = {
                "fwd_ms": tf * 1e3, "fwd_bwd_ms": tb * 1e3,
                "fwd_tflops": flops / tf / 1e12,
                "fwd_bwd_tflops": 3 * flops / tb / 1e12}
            print(impl, rows, out[f"gmm_{impl}_rows{rows}"], flush=True)
        a = grouped_matmul(lhs, rhs, sizes, impl="pallas")
        c = grouped_matmul(lhs, rhs, sizes, impl="ragged_dot")
        out[f"gmm_impls_max_abs_rows{rows}"] = float(
            jnp.abs(a.astype(jnp.float32) - c.astype(jnp.float32)).max())
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
