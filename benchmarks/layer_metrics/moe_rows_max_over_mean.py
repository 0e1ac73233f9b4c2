"""Imbalance over the experts held: the largest held expert's rows over the
held experts' mean, in the worst expert layer, at the window's last step
(the program's counter, read from the step's outputs after the window)."""


def read(ctx):
    moe = ctx["facts"].get("moe")
    if not moe:
        return None
    return float(moe["moe_rows_max_over_mean"])
