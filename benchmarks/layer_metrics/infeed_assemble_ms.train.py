"""Host time to assemble one batch (crop, flip, gather from the memory-mapped
shards), from the pump's ``assemble_s / assemble_n`` over the traced fit call."""


def read(ctx):
    span = ctx["facts"]["traced"]
    if not span or not span["counters"]["assemble_n"]:
        return None
    c = span["counters"]
    return 1e3 * c["assemble_s"] / c["assemble_n"]
