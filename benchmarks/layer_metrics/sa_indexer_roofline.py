"""The lightning indexer's share of its roofline: the least time a chip could
take for a step's indexer products (its scores at every causal entry, their
gradient at the selected entries, the KL target's q.k at the selected entries;
harness/work_sa.py, nothing recomputed) over the device time per step of
everything under ``sa.indexer``: the projections, the scores, the top-k
selection, the KL and its gradient, whatever implements them."""

from harness import work_sa


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    by = facts.get("scope_seconds")
    if not by or t is None or peaks is None or not t.steps \
            or by.get("sa.indexer", 0) <= 0:
        return None
    least = work_sa.indexer_min_seconds(
        facts["model_config"], facts["sequence_length"],
        facts["global_batch"] // facts["chips"], facts["dtype_bytes"], peaks)
    return 100.0 * least / (by["sa.indexer"] / t.steps)
