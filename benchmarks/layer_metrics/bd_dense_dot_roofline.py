"""The dense products' share of their roofline in a block-diffusion cell: the
least time a chip could take for the step's products of activations with a
matrix every position passes through (harness/work_bd.py
``dense_min_seconds``: the attention's four projections and the routers at
the 2L positions of ``[x_t | x_0]``, the head at the noisy half's L; 6 p t
FLOPs, nothing recomputed counts) over the device time per step of the ops
the trace classes as convolution or dot (fusions around them included),
which run each block's forward twice (the block is rematerialised). The
attention and the grouped products are custom calls and have shares of their
own."""

from harness import work_bd


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    if t is None or peaks is None or not t.steps or t.matmul_s <= 0 \
            or "bd_entries" not in facts:
        return None
    least = work_bd.dense_min_seconds(
        facts["model_config"], facts["sequence_length"],
        facts["global_batch"] // facts["chips"], facts["dtype_bytes"], peaks)
    return 100.0 * least / (t.matmul_s / t.steps)
