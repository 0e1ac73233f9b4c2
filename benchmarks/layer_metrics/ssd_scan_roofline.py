"""The Mamba-2 scans' share of their roofline: the least time a chip could
take for a step's scans (harness/work_hybrid.py ``scan_min_seconds``: per
pass the larger of the chunked products' FLOPs at the configuration's chunk
size over the peak rate and the bytes of x', B, C, dt and z in and y out over
the HBM rate, forward and backward, nothing recomputed) over the device time
per step of EVERYTHING under ``ssm.scan``, the rematerialised forward
included: it reads the same work whatever implements it."""

from harness import work_hybrid


def read(ctx):
    facts, t, peaks = ctx["facts"], ctx["trace"], ctx["peaks"]
    by = facts.get("scope_seconds")
    if not by or t is None or peaks is None or not t.steps \
            or by.get("ssm.scan", 0) <= 0:
        return None
    least = work_hybrid.scan_min_seconds(
        facts["model_config"], facts["sequence_length"],
        facts["global_batch"] // facts["chips"], facts["dtype_bytes"], peaks)
    return 100.0 * least / (by["ssm.scan"] / t.steps)
