"""Share of the traced steps' device time spent in operations under the
engine's ``optimizer`` scope (clipping, the optax update, the parameters'
update)."""


def read(ctx):
    by = ctx["facts"].get("scope_seconds")
    if not by or by["all"] <= 0:
        return None
    return 100.0 * by["optimizer"] / by["all"]
