"""The held experts' grouped products' share of their roofline in a
``nemotron_h``-family cell: the least time a chip could take for them (two
products an expert, rows of the latent width) at the rows really routed here
(harness/work_hybrid.py, from the program's ``moe_local_rows``), over the
device time per step of everything under ``moe.experts``: the sort, the
gather, the grouped products, the scatter-add, whatever implements them
(``expert_gmm_roofline``'s reading, at this family's shapes)."""

from harness import work_hybrid


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    by = facts.get("scope_seconds")
    if not by or t is None or peaks is None or not t.steps \
            or by.get("moe.experts", 0) <= 0:
        return None
    least = work_hybrid.expert_min_seconds(
        facts["model_config"], facts["moe"]["moe_local_rows"]
        / facts["chips"], facts["dtype_bytes"], peaks)
    return 100.0 * least / (by["moe.experts"] / t.steps)
