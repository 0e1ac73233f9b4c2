"""Share of the traced steps' device time spent in operations under the
``attn.gqa`` scope: the five projections, the q and k norms, RoPE, the flash
kernels (forward and backward), the gate and the output projection, of every
block."""


def read(ctx):
    by = ctx["facts"].get("scope_seconds")
    if not by or by["all"] <= 0 or "attn.gqa" not in by:
        return None
    return 100.0 * by["attn.gqa"] / by["all"]
