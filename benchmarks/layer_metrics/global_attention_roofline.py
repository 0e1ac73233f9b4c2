"""The global layers' attention products' share of their roofline: the least
time a chip could take for a step's causal q.k^T and p.v products of the
``full_attention`` layers, forward and backward (harness/work_gqa.py; nothing
recomputed counts), over the device time per step of the kernels (custom
calls) under ``attn.global``, which rebuild the scores in the backward (twice
where dQ has a launch of its own)."""

from harness import work_gqa


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    by = facts.get("scope_seconds")
    if not by or t is None or peaks is None or not t.steps \
            or by.get("attn.global:kernels", 0) <= 0:
        return None
    least = work_gqa.attention_min_seconds(
        facts["model_config"], facts["sequence_length"],
        facts["global_batch"] // facts["chips"], facts["dtype_bytes"], peaks,
        "full_attention")
    return 100.0 * least / (by["attn.global:kernels"] / t.steps)
