"""Share of the traced steps' device time spent in operations under the
``attn.mla`` scope (projections, RoPE, the flash kernels, forward,
rematerialised forward and backward; the MTP block's attention too)."""


def read(ctx):
    by = ctx["facts"].get("scope_seconds")
    if not by or by["all"] <= 0:
        return None
    return 100.0 * by["attn.mla"] / by["all"]
