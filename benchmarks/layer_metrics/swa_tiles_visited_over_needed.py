"""(q, k) tiles the windowed flash call sites' grids compute over the tiles
that hold an entry inside the window, forward and backward kernels, each at
its own tile size (the program's counter
``zoo_attention_window_tiles_total``, counted where a call site is traced).
1 where the grids walk the bands alone; a kernel that walked the whole causal
triangle would read about 4 at 16384 positions and a window of 2048."""


def read(ctx):
    tiles = ctx["facts"].get("window_tiles")
    if not tiles or tiles.get("needed", 0) <= 0:
        return None
    return tiles["visited"] / tiles["needed"]
