"""Share of the traced steps' device time spent in operations under the
expert layers' scopes ``moe.router``, ``moe.experts`` (sort, gather, grouped
products, scatter-add) and ``moe.shared``."""


def read(ctx):
    by = ctx["facts"].get("scope_seconds")
    if not by or by["all"] <= 0:
        return None
    return 100.0 * (by["moe.router"] + by["moe.experts"]
                    + by["moe.shared"]) / by["all"]
