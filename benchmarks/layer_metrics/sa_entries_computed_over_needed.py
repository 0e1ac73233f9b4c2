"""Score entries the selected-key flash walks compute over the entries the
selection keeps, a head and a forward pass, summed over the layers, as the
window's last step counted them (the program's ``sa_stats`` outputs, which
``zoo_attention_sa_entries_total`` adds up): the walk visits each (q, k)
tile that holds a selected entry, whole, so what is left over is the
unselected part of those tiles. A walk of every causal tile reads
``L (L + 1) / 2`` over the selected entries, about 4.3 at 16384 positions
and top-2048."""


def read(ctx):
    entries = ctx["facts"].get("sa_entries")
    if not entries or entries.get("needed", 0) <= 0:
        return None
    return entries["computed"] / entries["needed"]
