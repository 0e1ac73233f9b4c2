"""The block-diffusion attention's share of its roofline: the least time a
chip could take for a step's q.k^T and p.v products over ``[x_t | x_0]`` at
the block-diffusion mask's OWN score entries (L^2 + L B a head: the noisy
block's keys and the clean blocks before it for a noisy query, the clean
blocks up to its own for a clean one), forward and backward
(harness/work_bd.py; nothing recomputed, no walked tile's masked entry
counts), over the device time per step of everything under ``attn.bd``: the
flash kernels and what lays their operands out, whatever implements them."""

from harness import work_bd


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    by = facts.get("scope_seconds")
    if not by or t is None or peaks is None or not t.steps \
            or by.get("attn.bd", 0) <= 0:
        return None
    least = work_bd.attention_min_seconds(
        facts["model_config"], facts["sequence_length"],
        facts["global_batch"] // facts["chips"], facts["dtype_bytes"], peaks)
    return 100.0 * least / (by["attn.bd"] / t.steps)
