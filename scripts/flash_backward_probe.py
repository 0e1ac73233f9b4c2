#!/usr/bin/env python
"""The flash backward's paths side by side on the chip, at one shape.

    chiprun -- python scripts/flash_backward_probe.py [--seq 16384]
        [--heads 32] [--kv-heads 4] [--dim 128] [--window 2048]

For the causal mask and, with ``--window``, the windowed one: the gradient
of ``flash_attention`` (bfloat16) as ``_flash_bwd`` chooses it by bytes
(``fused``, ``fused_by_head`` or ``two_kernel``) and as the dQ + dK/dV pair
(the budget set to 0 for that trace alone): device ms a launch of every
custom call from a profiler trace, whether the three gradients are the
pair's to the bit, and what ``zoo_attention_backward_total`` counted. The
defaults are the grouped-query cell's (``trinity_mini.fit.packed16k``).
Writes ``chiprun_out/flash_backward_probe.json``. Refuses any platform but
``tpu``: a time comes from the chip.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def launches_ms(fn, args, n=3):
    """Mean device ms of each custom call over ``n`` traced runs, in launch
    order, and the results of the last."""
    import jax
    jax.block_until_ready(fn(*args))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    seen = {}
    for f in glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb")):
        for plane in jax.profiler.ProfileData.from_file(f).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    if "custom-call" in ev.name:
                        seen.setdefault(ev.name[:40], []).append(
                            ev.duration_ns / 1e6)
    return {k: round(sum(v) / len(v), 3) for k, v in seen.items()}, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "flash_backward_probe.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.ops.attention as attn
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"flash_backward_probe: platform {dev.platform!r}; no result",
              file=sys.stderr)
        return 3
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    shape = lambda h: (1, args.seq, h, args.dim)          # noqa: E731
    q, k, v, w = (jax.random.normal(key, shape(h), jnp.bfloat16)
                  for key, h in zip(keys, (args.heads, args.kv_heads,
                                           args.kv_heads, args.heads)))
    counts = lambda: {                                    # noqa: E731
        p: int(c.value) for p, c in (
            ("fused", attn._BACKWARD_FUSED),
            ("fused_by_head", attn._BACKWARD_FUSED_BY_HEAD),
            ("two_kernel", attn._BACKWARD_TWO_KERNEL))}
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "shape": vars(args)}
    for window in (None, args.window):
        def grad():
            return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                attn.flash_attention(q, k, v, causal=True, window=window
                                     ).astype(jnp.float32) * w), (0, 1, 2)))
        rec, results = {}, {}
        budget = attn._FUSED_BWD_DQ_BYTES
        for name, held in (("chosen", budget), ("pair", 0)):
            attn._FUSED_BWD_DQ_BYTES, before = held, counts()
            try:
                rec[name + "_ms"], results[name] = launches_ms(
                    grad(), (q, k, v))
            finally:
                attn._FUSED_BWD_DQ_BYTES = budget
            rec[name + "_path"] = [p for p, n in counts().items()
                                   if n != before[p]]
        rec["equal_to_the_bit"] = [bool(jnp.all(a == b)) for a, b in zip(
            results["chosen"], results["pair"])]
        rec["finite"] = [bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
                         for a in results["chosen"]]
        report["global" if window is None else f"window_{window}"] = rec
        print(window, json.dumps(rec), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
