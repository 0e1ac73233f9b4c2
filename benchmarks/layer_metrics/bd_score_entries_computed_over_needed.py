"""Score entries the block-diffusion flash call sites' grids compute over the
entries the mask keeps, forward and backward kernels, each at its own tile
size (the program's counter ``zoo_attention_bd_entries_total``, counted where
a call site is traced). The grids walk only the tiles that hold a visible
entry, so what is left over is the masked part of the tiles the mask crosses:
the noisy diagonal's tiles, a block wide inside a tile's width, and the clean
quadrants' diagonal tiles. A plain causal walk over the 2L positions would
read about 2; the whole square about 4."""


def read(ctx):
    entries = ctx["facts"].get("bd_entries")
    if not entries or entries.get("needed", 0) <= 0:
        return None
    return entries["computed"] / entries["needed"]
