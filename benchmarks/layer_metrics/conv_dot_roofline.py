"""The convolutions' and dense layers' share of their roofline: the least time
a chip could take for them on its share of the batch (harness/work.py: per
layer and per product the larger of FLOPs over the peak rate and bytes over
the HBM rate, forward and backward) over the device time per step of the ops
the trace classes as convolution or dot (fusions around them included)."""

from harness import work


def read(ctx):
    t, facts, peaks = ctx["trace"], ctx["facts"], ctx["peaks"]
    if t is None or peaks is None or not t.steps or t.matmul_s <= 0:
        return None
    least = work.min_step_seconds(
        facts["layers"], facts["global_batch"] // facts["chips"],
        facts["dtype_bytes"], peaks)["seconds"]
    return 100.0 * least / (t.matmul_s / t.steps)
