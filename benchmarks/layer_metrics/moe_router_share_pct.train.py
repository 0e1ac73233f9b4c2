"""Share of the traced steps' device time spent in operations under the
``moe.router`` scope alone: the scores over all experts, the top-k, the
gates, the correction bias's update (a count of every token-choice)."""


def read(ctx):
    by = ctx["facts"].get("scope_seconds")
    if not by or by["all"] <= 0 or "moe.router" not in by:
        return None
    return 100.0 * by["moe.router"] / by["all"]
