"""95th percentile of the gaps between the starts of consecutive train-step
programs on device 0, from the device trace: of the traced fit call's eleven
intervals (``statistics.quantiles(n=20)[-1]``, which interpolates)."""

import statistics


def read(ctx):
    t = ctx["trace"]
    if t is None or len(t.step_intervals_s) < 10:
        return None
    return 1e3 * statistics.quantiles(t.step_intervals_s, n=20)[-1]
