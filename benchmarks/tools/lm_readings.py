#!/usr/bin/env python3
"""The readings that a token cell's limits for `correct` are set from, taken
on the chip at the cell's own size, in one process (``tools/chip_readings.py``
does the same for the image cells):

* for every seed, the program's first steps against the plain reference;
* for the first ``--controls`` seeds, the control (the reference through
  8-bit float products) and the faults planted in the reference put in the
  program's place (half of the batch's sequences left out; the MTP head's
  loss left out) against the same reference.

``--trace-seconds S`` first runs a traced window on a seed of its own and
prints what the trace holds: the stats a few operations' metadata carry (by
hand: which of them is the scope path), the device time by scope, the
reduction's summary.

    chiprun -- python benchmarks/tools/lm_readings.py --workload <cell> \
        --seeds 3 --controls 2 --out chiprun_out/readings_<cell>.json
"""

import argparse
import glob
import json
import os
import shutil
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
    ap.add_argument("--first-seed", type=int, default=2_350_000_011)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--out", required=True)
    ap.add_argument("--trace-seconds", type=float, default=0.0)
    ap.add_argument("--cluster-mode", default="tpu")
    args = ap.parse_args()

    from harness import fit_cell, lm_fit_cell, scopes, spec, trace
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
                                     "bytes_reserved", "peak_bytes_reserved",
                                     "bytes_limit")}
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

    if args.trace_seconds > 0:
        scratch = os.path.join(
            os.environ.get("TMPDIR") or os.path.join(spec.REPO_ROOT,
                                                     ".bench_tmp"),
            f"readings_{cell.name}")
        shutil.rmtree(scratch, ignore_errors=True)
        prep = lm_fit_cell.prepare(cell, args.first_seed - 1, mesh, devices)
        mem("prepared")
        lm_fit_cell.first_steps(prep)
        mem("first steps")
        spans = fit_cell.window(prep, args.trace_seconds,
                                os.path.join(scratch, "trace"))
        row = {"traced_window": spans, "mem_after_window": mem("window")}
        for f in glob.glob(os.path.join(scratch, "trace", "plugins",
                                        "profile", "*", "*.xplane.pb")):
            print("trace", f, os.path.getsize(f), flush=True)
            row["stats_seen"] = scopes.stat_names_seen(f, 6)
            row["scope_seconds"] = scopes.scope_seconds(
                f, lm_fit_cell.SCOPES)
            r = trace.reduce_xplane(f, cell.chips)
            row["reduction"] = None if r is None else {
                "window_s": r.window_s, "busy_s": r.busy_s,
                "steps": r.steps, "step_program": r.step_program,
                "step_intervals_s": r.step_intervals_s,
                "matmul_s": r.matmul_s, "top_ops": r.op_seconds[:40],
                "idle_gaps": r.idle_gaps}
        print(json.dumps(row, default=str)[:12000], flush=True)
        rows_out.append(row)
        save()
        fit_cell.free_program(prep)
        shutil.rmtree(scratch, ignore_errors=True)
    batch = int(cell.config["per_chip_batch"]) * cell.chips
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ts = time.perf_counter()
        prep = lm_fit_cell.prepare(cell, seed, mesh, devices)
        lm_fit_cell.first_steps(prep)
        row = {"seed": seed, "mem_after_steps": mem("first steps"),
               "setup_s": time.perf_counter() - ts}
        program = prep.program
        fit_cell.free_program(prep)
        tr = time.perf_counter()
        ref = lm_fit_cell.reference_readings(prep)
        row["reference_s"] = time.perf_counter() - tr
        row["mem_after_reference"] = mem("reference")
        row["losses"] = {"program": program["losses"],
                         "reference": ref["losses"],
                         "reference_main": ref["main_losses"],
                         "reference_mtp": ref["mtp_losses"]}
        row["program"] = lm_fit_cell.compare_sides(program, ref, prep.shapes,
                                                   reference)
        if i < args.controls:
            for name, kw in (("control_fp8", {"quant": nn.fp8_quant}),
                             ("fault_half_batch", {"rows": batch // 2}),
                             ("fault_no_mtp_loss", {"drop_mtp": True})):
                side = lm_fit_cell.reference_readings(prep, **kw)
                row[name] = lm_fit_cell.compare_sides(side, ref, prep.shapes,
                                                      reference)
        row["seconds"] = time.perf_counter() - ts
        rows_out.append(row)
        print(json.dumps(row, default=str)[:4000], flush=True)
        save()
    save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
