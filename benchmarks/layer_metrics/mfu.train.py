"""The whole step's share of the chips' peak: the FLOPs the forward and
backward passes require per sample (harness/work.py, from the configuration's
layer list, nothing recomputed) times the samples of the traced steps, over
the traced window (first op's start to last op's end, whatever the device did
in between) times chips times the peak bf16 rate."""


def read(ctx):
    t, facts, peaks = ctx["trace"], ctx["facts"], ctx["peaks"]
    if t is None or peaks is None or t.window_s <= 0 or not t.steps:
        return None
    flops = facts["train_flops_per_sample"] * t.steps * facts["global_batch"]
    return 100.0 * flops / (t.window_s * facts["chips"]
                            * peaks["bf16_flops_per_s"])
