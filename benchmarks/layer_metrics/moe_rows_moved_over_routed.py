"""Rows the expert layers' gathers fetched over the token-choices the router
sent to the experts held, over the steps the program counted (its counter,
read from the step's outputs after the window): 1 is no padding moved. A
program that does not count it reports nothing."""


def read(ctx):
    value = (ctx["facts"].get("moe") or {}).get("moe_rows_moved_over_routed")
    return None if value is None else float(value)
