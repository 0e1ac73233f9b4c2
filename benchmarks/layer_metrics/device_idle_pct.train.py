"""Share of the traced window in which no operation ran, on the device with
the largest such share."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s_least / t.window_s)
