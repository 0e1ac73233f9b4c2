#!/usr/bin/env python
"""Learned sparse attention's parts alone on the chip, at the
``keye_vl2_30b_a3b.fit.sa16k`` cell's shape (one 16384-token sequence, 32
query heads on 4 kv heads of 128, the indexer's 16 heads of 64, top-2048,
chunks of 512), one layer, on random inputs.

    python scripts/sa_select_probe.py [--length 16384] [--iters 5]
        [--out report.json]

Times, in milliseconds of the host's clock around calls that end in
``block_until_ready`` (after a warm-up call each), the median of
``--iters``:

* ``select``: the indexer's scores and the exact top-k selection, packed
  (``ops/sparse_attention.py indexer_select``: bisection on the scores'
  bits, no sort), one layer's;
* ``select_sort``: the same scores with ``lax.top_k``'s selection in place
  of the bisection (what a sort costs, for comparison; the same bits);
* ``attention``: the selected-key flash kernels, forward and backward
  (``jax.grad`` of a weighted sum of the output);
* ``indexer_loss``: the KL loss with its gradients, one launch of the
  kernel over the causal tiles (``_kl_and_grads``, what ``indexer_kl``
  runs in the forward);

and the selected-key walk's entries computed over those selected. Prints a
line a part; ``--out`` writes them as JSON too. Refuses any platform but
``tpu``: a time comes from the chip.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _timed(fn, args, iters):
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t))
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--length", type=int, default=16384)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax
    if jax.devices()[0].platform != "tpu":
        print("sa_select_probe.py: a time comes from the chip; JAX found "
              f"{jax.devices()[0].platform!r}", file=sys.stderr)
        return 3
    from analytics_zoo_tpu.ops import attention, sparse_attention as sa

    length, heads, kv, d, hi, di, rows = (args.length, 32, 4, 128, 16, 64,
                                          512)
    topk = min(2048, length)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16
    q_i = jax.random.normal(keys[0], (1, length, hi, di), bf)
    k_i = jax.random.normal(keys[1], (1, length, di), bf)
    w = jax.random.normal(keys[2], (1, length, hi), jnp.float32) / 32
    q = jax.random.normal(keys[3], (1, length, heads, d), bf)
    k = jax.random.normal(keys[4], (1, length, kv, d), bf)
    v = jax.random.normal(keys[5], (1, length, kv, d), bf)
    g = jax.random.normal(keys[6], (1, length, heads, d), bf)

    select = jax.jit(lambda a, b, c: sa.indexer_select(a, b, c, topk, rows))

    def select_sort(q_i, w, k_i):
        def chunk(c):
            t0 = c * rows
            scores = sa._chunk_scores(
                lax.dynamic_slice_in_dim(q_i, t0, rows, 1),
                lax.dynamic_slice_in_dim(w, t0, rows, 1), k_i, t0)
            vals, idx = lax.top_k(scores, topk)
            keep = jnp.zeros(scores.shape, bool).at[
                jnp.arange(1)[:, None, None], jnp.arange(rows)[None, :, None],
                idx].set(vals > -jnp.inf)
            return attention.pack_selection(keep, rows)
        words = lax.map(chunk, jnp.arange(length // rows))
        return jnp.moveaxis(words, 0, 1).reshape(1, length // 32, length)

    bits = select(q_i, w, k_i)
    same = bool(jnp.all(jax.jit(select_sort)(q_i, w, k_i) == bits))

    def attend(q, k, v, bits):
        def loss(q, k, v):
            out = attention.flash_attention(q, k, v, mask=attention.SELECTED,
                                            selection=bits, block=rows)
            return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    _, lse = attention.flash_attention(q, k, v, mask=attention.SELECTED,
                                       selection=bits, block=rows,
                                       return_lse=True)
    kl = jax.jit(lambda *a: sa._kl_and_grads(rows, d ** -0.5, *a))
    computed, needed = attention.selection_entries(bits, rows)
    report = {
        "device": jax.devices()[0].device_kind, "length": length,
        "select_ms": _timed(select, (q_i, w, k_i), args.iters),
        "select_sort_ms": _timed(jax.jit(select_sort), (q_i, w, k_i),
                                 args.iters),
        "sort_selects_the_same_bits": same,
        "attention_ms": _timed(jax.jit(attend), (q, k, v, bits), args.iters),
        "indexer_loss_ms": _timed(kl, (q_i, w, k_i, q, k, lse, bits),
                                  args.iters),
        "entries_computed_over_needed": float(computed / needed),
    }
    for key, value in report.items():
        print(f"{key} {value}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
