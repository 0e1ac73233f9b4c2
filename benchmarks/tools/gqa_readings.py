#!/usr/bin/env python3
"""The readings that a grouped-query token cell's limits for `correct` are
set from, taken on the chip at the cell's own size, in one process
(``tools/lm_readings.py`` does the same for the MLA cell):

* for every seed, the program's first steps against the plain reference;
* for the first ``--controls`` seeds, the control (the reference through
  8-bit float products) and the faults planted in the reference put in the
  program's place (``gqa_lm_fit_cell.FAULTS``: the sliding layers run plain
  causal, RoPE on the global layer too, half of the batch left out) against
  the same reference.

    chiprun -- python benchmarks/tools/gqa_readings.py --workload <cell> \
        --seeds 3 --controls 2 --out chiprun_out/readings_<cell>.json
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_390_000_011)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cluster-mode", default="tpu")
    args = ap.parse_args()

    from harness import fit_cell, gqa_lm_fit_cell as driver, spec
    from reference import nn
    cell = spec.load_cell(args.workload)
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
        row["program"] = driver.compare_sides(program, ref, prep.shapes,
                                              reference)
        if i < args.controls:
            sides = [("control_fp8", {"quant": nn.fp8_quant})] + [
                (f"fault_{f}", {"fault": f}) for f in driver.FAULTS]
            for name, kw in sides:
                side = driver.reference_readings(prep, **kw)
                row[name] = driver.compare_sides(side, ref, prep.shapes,
                                                 reference)
        row["seconds"] = time.perf_counter() - ts
        rows_out.append(row)
        print(json.dumps(row, default=str)[:6000], flush=True)
        save()
    save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
