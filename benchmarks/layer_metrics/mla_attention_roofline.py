"""The attention products' share of their roofline: the least time a chip
could take for a step's causal q.k^T and p.v products of every block,
forward and backward (harness/work_lm.py; nothing recomputed counts), over
the device time per step of the kernels (custom calls) under ``attn.mla``,
which run the forward twice (the block is rematerialised) and rebuild the
scores in the backward."""

from harness import work_lm


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    by = facts.get("scope_seconds")
    if not by or t is None or peaks is None or not t.steps \
            or by["attn.mla:kernels"] <= 0:
        return None
    least = work_lm.attention_min_seconds(
        facts["model_config"], facts["sequence_length"],
        facts["global_batch"] // facts["chips"], facts["dtype_bytes"], peaks)
    return 100.0 * least / (by["attn.mla:kernels"] / t.steps)
