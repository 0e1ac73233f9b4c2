"""Share of the traced steps' device time spent in operations under the
``sa.indexer`` scope: the indexer's projections, its scores, the top-k
selection, its KL loss and that loss's gradient, of every layer."""


def read(ctx):
    by = ctx["facts"].get("scope_seconds")
    if not by or by["all"] <= 0 or "sa.indexer" not in by:
        return None
    return 100.0 * by["sa.indexer"] / by["all"]
