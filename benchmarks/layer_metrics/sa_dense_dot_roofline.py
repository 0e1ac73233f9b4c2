"""The dense products' share of their roofline in a learned-sparse-attention
cell: the least time a chip could take for the step's products of
activations with a matrix every token passes through, outside the indexer
(harness/work_sa.py ``dense_min_seconds``: the attention's four projections,
the routers, the head; 6 p t FLOPs, nothing recomputed counts) over the
device time per step of the ops the trace classes as convolution or dot
(fusions around them included) OUTSIDE ``sa.indexer`` (the indexer's
products have ``sa_indexer_roofline``), which run each block's forward
twice (the block is rematerialised). The attention and the grouped products
are custom calls and have shares of their own."""

from harness import work_sa


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    indexer = facts.get("sa_indexer_matmul_s")
    if t is None or peaks is None or not t.steps or indexer is None \
            or t.matmul_s - indexer <= 0:
        return None
    tokens = facts["sequence_length"] * facts["global_batch"] \
        // facts["chips"]
    least = work_sa.dense_min_seconds(facts["model_config"], tokens,
                                      facts["dtype_bytes"], peaks)
    return 100.0 * least / ((t.matmul_s - indexer) / t.steps)
