"""The sliding-window layers' attention products' share of their roofline:
the least time a chip could take for a step's q.k^T and p.v products of the
sliding layers, forward and backward, at the WINDOW's own score entries (the
sum over t of min(t + 1, sliding_window); harness/work_gqa.py; nothing
recomputed, no tile's masked corner counts), over the device time per step of
the kernels (custom calls) under ``attn.window``, which rebuild the scores in
the backward (twice where dQ has a launch of its own)."""

from harness import work_gqa


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    by = facts.get("scope_seconds")
    if not by or t is None or peaks is None or not t.steps \
            or by.get("attn.window:kernels", 0) <= 0:
        return None
    least = work_gqa.attention_min_seconds(
        facts["model_config"], facts["sequence_length"],
        facts["global_batch"] // facts["chips"], facts["dtype_bytes"], peaks,
        "sliding_attention")
    return 100.0 * least / (by["attn.window:kernels"] / t.steps)
