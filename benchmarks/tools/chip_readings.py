#!/usr/bin/env python3
"""The readings that a cell's limits for `correct` are set from, taken on the
chip at the cell's own size, in one process:

* for every seed, the program's first steps against the plain reference
  (their largest reading over the seeds is a limit's lower reading);
* for the first ``--controls`` seeds, the control (the reference computed
  through 8-bit float products, the precision next below bfloat16) and the
  faults planted in the reference put in the program's place (half of the
  batch left out; on several chips, one chip's shard alone: the exchange left
  out) against the same reference (their smallest is the upper reading).

Writes one JSON file; PERF.md quotes it. Run through the chip tool::

    chiprun -- python benchmarks/tools/chip_readings.py --workload <cell> \
        --seeds 12 --controls 3 --out chiprun_out/readings_<cell>.json

``--trace-out DIR`` also runs a short traced window on the first seed and
copies its ``xplane.pb`` there (the recorded trace of tests/test_trace.py).
"""

import argparse
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--trace-out")
    ap.add_argument("--trace-seconds", type=float, default=8.0)
    ap.add_argument("--cluster-mode", default="tpu")
    args = ap.parse_args()

    from harness import dataset, fit_cell, spec
    from reference import nn
    cell = spec.load_cell(args.workload)
    import jax
    t0 = time.perf_counter()
    mesh, devices = fit_cell.open_context(cell, args.cluster_mode)
    scratch = os.path.join(os.environ.get("TMPDIR") or "/tmp",
                           f"readings_{cell.name}")
    rows_out = []
    batch = int(cell.config["per_chip_batch"]) * cell.chips

    def mem():
        s = devices[0].memory_stats() or {}
        out = {k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                     "bytes_reserved", "peak_bytes_reserved")}
        with open("/proc/self/status") as f:
            out["host_rss_kb"] = next(int(line.split()[1]) for line in f
                                      if line.startswith("VmRSS"))
        print(f"[{time.perf_counter() - t0:8.1f}] mem {out}", flush=True)
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

    if args.trace_out:
        prep = fit_cell.prepare(cell, args.first_seed - 1, mesh, devices,
                                os.path.join(scratch, "data"))
        fit_cell.first_steps(prep)
        mem()
        tdir = os.path.join(scratch, "trace")
        spans = fit_cell.window(prep, args.trace_seconds, tdir)
        rows_out.append({"traced_window": spans, "mem_after_window": mem()})
        save()
        os.makedirs(args.trace_out, exist_ok=True)
        import glob
        for f in glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                        "*.xplane.pb")):
            print("trace", f, os.path.getsize(f), flush=True)
            import subprocess
            p = subprocess.run(
                [sys.executable, os.path.join(TOOLS, "trim_trace.py"), f,
                 "--out", os.path.join(args.trace_out, "cell.xplane.pb"),
                 "--steps", "3"], capture_output=True, text=True,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            with open(os.path.join(args.trace_out, "by_hand.txt"), "w") as fh:
                fh.write(p.stdout + "\n" + p.stderr[-3000:])
            print(p.stdout[-6000:], p.stderr[-1500:], flush=True)
        fit_cell.free_program(prep)
        mem()
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ts = time.perf_counter()
        prep = fit_cell.prepare(cell, seed, mesh, devices,
                                os.path.join(scratch, "data"))
        fit_cell.first_steps(prep)
        row = {"seed": seed, "mem_after_steps": mem()}
        bad = dataset.count_bad_rows(dataset.ShardIndex(prep.data_dir),
                                     prep.fed)
        program = prep.program
        fit_cell.free_program(prep)
        row["mem_after_free"] = mem()
        tr = time.perf_counter()
        ref = fit_cell.reference_readings(prep)
        row["reference_s"] = time.perf_counter() - tr
        row["mem_after_reference"] = mem()
        row["program"] = fit_cell.compare_sides(program, ref, prep.shapes)
        row["program"]["infeed_bad_rows"] = bad["bad"]
        row["losses"] = {"program": program["losses"],
                         "reference": ref["losses"]}
        if i < args.controls:
            ctl = fit_cell.reference_readings(prep, quant=nn.fp8_quant)
            row["control_fp8"] = fit_cell.compare_sides(ctl, ref, prep.shapes)
            half = fit_cell.reference_readings(prep, rows=batch // 2)
            row["fault_half_batch"] = fit_cell.compare_sides(half, ref, prep.shapes)
            if cell.chips > 1:
                one = fit_cell.reference_readings(prep,
                                                  rows=batch // cell.chips)
                row["fault_no_exchange"] = fit_cell.compare_sides(one, ref, prep.shapes)
        row["seconds"] = time.perf_counter() - ts
        rows_out.append(row)
        print(json.dumps(row, default=str)[:2500], flush=True)
        save()
        shutil.rmtree(prep.data_dir, ignore_errors=True)
    shutil.rmtree(scratch, ignore_errors=True)
    save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
