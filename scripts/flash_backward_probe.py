#!/usr/bin/env python
"""The flash backward's paths side by side on the chip, at one shape.

    chiprun -- python scripts/flash_backward_probe.py [--seq 16384]
        [--heads 32] [--kv-heads 4] [--dim 128] [--window 2048]
        [--mask block_diffusion --block 4]

For the causal mask and, with ``--window``, the windowed one (or, with
``--mask block_diffusion``, the block-diffusion mask over ``[x_t | x_0]`` in
blocks of ``--block``): the gradient of ``flash_attention`` (bfloat16) as
``_flash_bwd`` chooses it by bytes (``fused``, ``fused_by_head`` or
``two_kernel``) and as the dQ + dK/dV pair (the budget set to 0 for that
trace alone): device ms a launch of every custom call from a profiler trace,
the grid steps each launch issues, whether the three gradients are the
pair's to the bit, and what ``zoo_attention_backward_total`` counted. The
defaults are the grouped-query cell's (``trinity_mini.fit.packed16k``);
``--mask block_diffusion --block 4`` at the same sizes is the
block-diffusion cell's (``sdar_30b_a3b.fit.bd8k``).

With the block-diffusion mask the probe also prints the tiles each launch
computes, and times the chosen gradient again with ``_bd_tiles``'s longest
walks doubled: where a grid's innermost extent is the longest walk, every
step that adds is idle, and the ms it adds over the steps it adds is the
cost of one idle grid step. Where the grid walks a list of the tiles it
computes the doubled walks add no step, and no cost is read.

Writes ``chiprun_out/flash_backward_probe.json``. Refuses any platform but
``tpu``: a time comes from the chip.
"""

import argparse
import glob
import json
import math
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


def grid_steps(fn, args):
    """The grid steps of each flash kernel launch of ``fn``, in launch
    order, read from the grids of its ``pallas_call``s."""
    import jax

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                if "flash" in eqn.params["jaxpr"].debug_info.func_name:
                    yield math.prod(eqn.params["grid_mapping"].grid)
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def bd_tiles_computed(attn, args, path):
    """Tiles each launch of a block-diffusion gradient computes: the
    forward's by q tile at its default tiles (1024 wide, or a half), the
    backward's at its own (the pair's dQ by q tile, its dK/dV and the fused
    launch by k block)."""
    half = args.seq // 2
    tile = min(1024, half)
    fwd = attn._bd_tiles(args.seq, tile, tile, half, args.block)
    bq, bk = attn._bwd_tile_sizes(args.seq, args.seq, tile, tile)
    bwd = attn._bd_tiles(args.seq, bq, bk, half, args.block)
    launches = [fwd["by_k"]] + ([bwd["by_k"], bwd["by_q"]]
                                if path == "two_kernel" else [bwd["by_q"]])
    return [args.heads * n for n in launches]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--mask", choices=("causal", "block_diffusion"),
                    default="causal")
    ap.add_argument("--block", type=int, default=4)
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
    if args.mask == "block_diffusion":
        masks = {"block_diffusion": dict(mask="block_diffusion",
                                         block=args.block)}
    else:
        masks = {"global": dict(causal=True),
                 f"window_{args.window}": dict(causal=True,
                                               window=args.window)}
    bd = args.mask == "block_diffusion"
    for label, mask in masks.items():
        def grad():
            return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                attn.flash_attention(q, k, v, **mask
                                     ).astype(jnp.float32) * w), (0, 1, 2)))
        rec, results = {}, {}
        budget = attn._FUSED_BWD_DQ_BYTES
        for name, held in (("chosen", budget), ("pair", 0)):
            attn._FUSED_BWD_DQ_BYTES, before = held, counts()
            try:
                rec[name + "_steps"] = grid_steps(grad(), (q, k, v))
                rec[name + "_ms"], results[name] = launches_ms(
                    grad(), (q, k, v))
            finally:
                attn._FUSED_BWD_DQ_BYTES = budget
            rec[name + "_path"] = [p for p, n in counts().items()
                                   if n != before[p]]
            if bd:
                rec[name + "_tiles"] = bd_tiles_computed(
                    attn, args, rec[name + "_path"][0])
        if bd:
            # the longest walks doubled: the chosen gradient's launches again
            tiles = attn._bd_tiles

            def doubled(*a):
                return dict(tiles(*a), k_steps=2 * tiles(*a)["k_steps"],
                            q_steps=2 * tiles(*a)["q_steps"])
            attn._bd_tiles = doubled
            try:
                rec["doubled_steps"] = grid_steps(grad(), (q, k, v))
                rec["doubled_ms"], _ = launches_ms(grad(), (q, k, v))
            finally:
                attn._bd_tiles = tiles
            rec["idle_step_us"] = [
                round(1000 * (ms2 - ms) / (n2 - n), 4) if n2 > n else None
                for ms, ms2, n, n2 in zip(
                    rec["chosen_ms"].values(), rec["doubled_ms"].values(),
                    rec["chosen_steps"], rec["doubled_steps"])]
        rec["equal_to_the_bit"] = [bool(jnp.all(a == b)) for a, b in zip(
            results["chosen"], results["pair"])]
        rec["finite"] = [bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
                         for a in results["chosen"]]
        report[label] = rec
        print(label, json.dumps(rec), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
