"""The dense products' share of their roofline in an ``afmoe``-family cell:
the least time a chip could take for the step's products of activations with
a matrix every token passes through (harness/work_gqa.py
``dense_min_seconds``: the attention's five projections, the dense layer's
feed-forward, the routers, the shared experts, the head; 6 p t FLOPs, nothing
recomputed counts) over the device time per step of the ops the trace
classes as convolution or dot (fusions around them included), which run each
block's forward twice (the block is rematerialised). The attention and the
grouped products are custom calls and have shares of their own."""

from harness import work_gqa


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    if t is None or peaks is None or not t.steps or t.matmul_s <= 0 \
            or "model_config" not in facts:
        return None
    tokens = facts["sequence_length"] * facts["global_batch"] \
        // facts["chips"]
    least = work_gqa.dense_min_seconds(facts["model_config"], tokens,
                                       facts["dtype_bytes"], peaks)
    return 100.0 * least / (t.matmul_s / t.steps)
