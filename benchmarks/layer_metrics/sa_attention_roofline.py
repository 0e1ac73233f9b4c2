"""The selected-key attention's share of its roofline: the least time a chip
could take for a step's q.k^T and p.v products at the SELECTED entries (each
query's top-k keys, ``sum_t min(t + 1, topk)`` a head), forward and backward
(harness/work_sa.py; nothing recomputed, no walked tile's unselected entry
counts), over the device time per step of everything under ``attn.sa``: the
flash kernels, the tile lists they walk and what lays their operands out."""

from harness import work_sa


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    by = facts.get("scope_seconds")
    if not by or t is None or peaks is None or not t.steps \
            or by.get("attn.sa", 0) <= 0:
        return None
    least = work_sa.attention_min_seconds(
        facts["model_config"], facts["sequence_length"],
        facts["global_batch"] // facts["chips"], facts["dtype_bytes"], peaks)
    return 100.0 * least / (by["attn.sa"] / t.steps)
