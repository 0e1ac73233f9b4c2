"""The dense products' share of their roofline in a ``nemotron_h``-family
cell: the least time a chip could take for the step's products of
activations with a matrix every token passes through (harness/work_hybrid.py
``dense_min_seconds``: the mixers' in and out projections, q/k/v/o, the
routers, the latent projections, the shared experts, the head; 6 p t FLOPs,
nothing recomputed counts) over the device time per step of the ops the
trace classes as convolution or dot (fusions around them included) OUTSIDE
``ssm.scan`` (the scan's own products have ``ssd_scan_roofline``), which run
each block's forward twice (the block is rematerialised). The attention and
the grouped products are custom calls and have shares of their own."""

from harness import work_hybrid


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    scan = facts.get("scan_matmul_s")
    if t is None or peaks is None or not t.steps or scan is None \
            or t.matmul_s - scan <= 0:
        return None
    tokens = facts["sequence_length"] * facts["global_batch"] \
        // facts["chips"]
    least = work_hybrid.dense_min_seconds(facts["model_config"], tokens,
                                          facts["dtype_bytes"], peaks)
    return 100.0 * least / ((t.matmul_s - scan) / t.steps)
