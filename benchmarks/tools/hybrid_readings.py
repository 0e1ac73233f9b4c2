#!/usr/bin/env python3
"""The readings that a ``nemotron_h`` token cell's limits for `correct` are
set from, taken on the chip at the cell's own size, in one process
(``tools/gqa_readings.py``'s way, with the driver the configuration names):

* for every seed, the program's first steps against the plain reference;
* for the first ``--controls`` seeds, the control (the reference through
  8-bit float products) and the faults planted in the reference put in the
  program's place (the driver's ``FAULTS``: the scan's state reset at every
  chunk boundary, the experts' ReLU not squared, half of the labels) against
  the same reference.

Beside each side's compared numbers, its ten worst leaves by the gap of
norms and by the distance (``leaves``), for choosing which numbers to limit.

    chiprun -- python benchmarks/tools/hybrid_readings.py --workload <cell> \
        --seeds 3 --controls 2 --out chiprun_out/readings_<cell>.json
"""

import argparse
import json
import os
import statistics
import sys
import time

TOOLS = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TOOLS)
sys.path.insert(0, os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)


def worst_leaves(side, ref, top=10):
    """A side's leaves by ``check.leaf_gaps`` and by the distance over the
    larger of the reference's leaf and median leaf, the worst first."""
    from harness import check
    out = {}
    for name, norm in (("grad", "grad1_norm"), ("dparam", "dparam_norm")):
        gaps = check.leaf_gaps(side[norm], ref[norm])
        out[f"{name}_gap"] = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        diff = side.get(f"{name}_diff")
        if diff is not None:
            med = statistics.median(ref[norm].values())
            rel = {k: diff[k] / max(w, med, 1e-30)
                   for k, w in ref[norm].items()}
            out[f"{name}_diff"] = sorted(rel.items(),
                                         key=lambda kv: -kv[1])[:top]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_390_000_011)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cluster-mode", default="tpu")
    args = ap.parse_args()

    from harness import fit_cell, spec
    from reference import nn
    cell = spec.load_cell(args.workload)
    driver = cell.load("driver")
    import jax
    t0 = time.perf_counter()
    mesh, devices = fit_cell.open_context(cell, args.cluster_mode)
    reference = cell.load("reference")
    rows_out = []

    def mem(tag):
        s = devices[0].memory_stats() or {}
        out = {k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                     "peak_bytes_reserved", "bytes_limit")}
        print(f"[{time.perf_counter() - t0:8.1f}] {tag} mem {out}",
              flush=True)
        return out

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
        row = {"seed": seed, "mem_after_steps": mem("first steps"),
               "setup_s": time.perf_counter() - ts}
        program = prep.program
        fit_cell.free_program(prep)
        tr = time.perf_counter()
        ref = driver.reference_readings(prep)
        row["reference_s"] = time.perf_counter() - tr
        row["mem_after_reference"] = mem("reference")
        row["losses"] = {"program": program["losses"],
                         "reference": ref["losses"]}
        row["program"] = compared(program, ref, prep.shapes)
        if i < args.controls:
            sides = [("control_fp8", {"quant": nn.fp8_quant})] + [
                (f"fault_{f}", {"fault": f}) for f in driver.FAULTS]
            for name, kw in sides:
                side = driver.reference_readings(prep, **kw)
                row[name] = compared(side, ref, prep.shapes)
        row["seconds"] = time.perf_counter() - ts
        rows_out.append(row)
        print(json.dumps({k: v for k, v in row.items()}, default=str)[:3000],
              flush=True)
        save()
    save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
