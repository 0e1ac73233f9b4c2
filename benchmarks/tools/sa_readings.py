#!/usr/bin/env python3
"""The readings that a learned-sparse-attention token cell's limits for
`correct` are set from, taken on the chip at the cell's own size, in one
process (``tools/hybrid_readings.py``'s way), with a choice of which faults
to read and on how many seeds:

* for every seed, the program's first steps against the plain reference;
* for the first ``--controls`` seeds, the control (the reference through
  8-bit float products, the indexer's scores among them);
* for the first ``--fault-seeds`` seeds, each fault of ``--faults`` (by
  default the driver's ``FAULTS``) planted in the reference.

Beside each side's compared numbers, its ten worst leaves by the gap of
norms and by the distance (``leaves``).

    python benchmarks/tools/sa_readings.py --workload <cell> --seeds 2 \
        --controls 2 --fault-seeds 1 \
        --faults dense_attention,no_causal_clip --out readings_<cell>.json
"""

import argparse
import json
import os
import sys
import time

TOOLS = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TOOLS)
sys.path.insert(0, os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, TOOLS)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_390_000_011)
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--faults", default="",
                    help="comma-separated; empty: the driver's FAULTS")
    ap.add_argument("--out", required=True)
    ap.add_argument("--cluster-mode", default="tpu")
    args = ap.parse_args()

    from harness import fit_cell, spec
    from hybrid_readings import worst_leaves
    from reference import nn
    cell = spec.load_cell(args.workload)
    driver = cell.load("driver")
    faults = [f for f in args.faults.split(",") if f] or list(driver.FAULTS)
    import jax
    t0 = time.perf_counter()
    mesh, devices = fit_cell.open_context(cell, args.cluster_mode)
    reference = cell.load("reference")
    rows_out = []

    def save():
        d = jax.devices()[0]
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "device": {
                "platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())},
                "seconds": time.perf_counter() - t0, "rows": rows_out}, f,
                indent=1, default=str)

    def compared(side, ref, shapes):
        numbers = driver.compare_sides(side, ref, shapes, reference)
        return dict(numbers, leaves=worst_leaves(side, ref))

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ts = time.perf_counter()
        prep = driver.prepare(cell, seed, mesh, devices)
        driver.first_steps(prep)
        row = {"seed": seed, "setup_s": time.perf_counter() - ts}
        program = prep.program
        fit_cell.free_program(prep)
        ref = driver.reference_readings(prep)
        row["losses"] = {"program": program["losses"],
                         "reference": ref["losses"]}
        row["program"] = compared(program, ref, prep.shapes)
        sides = [("control_fp8", {"quant": nn.fp8_quant})] \
            if i < args.controls else []
        if i < args.fault_seeds:
            sides += [(f"fault_{f}", {"fault": f}) for f in faults]
        for name, kw in sides:
            tr = time.perf_counter()
            row[name] = compared(driver.reference_readings(prep, **kw), ref,
                                 prep.shapes)
            row[name]["seconds"] = time.perf_counter() - tr
        row["seconds"] = time.perf_counter() - ts
        rows_out.append(row)
        print(json.dumps(row, default=str)[:3000], flush=True)
        save()
    save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
