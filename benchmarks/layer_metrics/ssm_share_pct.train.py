"""Share of the traced steps' device time spent in operations under the
``ssm.mixer`` scope: a Mamba-2 mixer's input projection, its convolution, the
scan (forward, rematerialised forward and backward), the gated norm and the
output projection, of every such block."""


def read(ctx):
    by = ctx["facts"].get("scope_seconds")
    if not by or by["all"] <= 0 or "ssm.mixer" not in by:
        return None
    return 100.0 * by["ssm.mixer"] / by["all"]
