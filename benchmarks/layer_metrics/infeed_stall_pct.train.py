"""Share of the traced fit call' wall time that ``fit`` spent waiting for the
infeed's next batch: the pump's ``stall_s`` (a host wait, sound) over the host
time of that call."""


def read(ctx):
    span = ctx["facts"]["traced"]
    if not span or span["seconds"] <= 0:
        return None
    return 100.0 * span["counters"]["stall_s"] / span["seconds"]
