"""Median gap between the starts of consecutive train-step programs on
device 0, from the device trace."""

import statistics


def read(ctx):
    t = ctx["trace"]
    if t is None or len(t.step_intervals_s) < 2:
        return None
    return 1e3 * statistics.median(t.step_intervals_s)
