"""Share of the traced window in which an all-reduce ran on a device while no
other operation did (the exchange that compute does not hide), averaged over
the devices. Nothing to read where the trace holds no all-reduce."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.window_s <= 0 or t.collective_s <= 0:
        return None
    return 100.0 * t.collective_exposed_s / t.window_s
