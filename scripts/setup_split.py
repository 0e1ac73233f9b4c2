#!/usr/bin/env python3
"""Where one run of a benchmark cell spends its set-up, from the program's own
set-up stages (``obs/trace.py stage``; PERF.md §5 "where set-up goes").

    chiprun -- python scripts/setup_split.py --workload <cell> --seed <n> \\
        [--seconds 20] [--trace 1] [--tag warm]

Runs the cell as ``benchmarks/run.py`` does (same driver, same result line as
the last line of standard output) and writes beside it, to
``chiprun_out/setup_split_<cell>_<tag>.json``: the run's ``setup_s`` and rate
(a traced line carries per-layer metrics only), the per-layer metrics,
every stage's self seconds and count, JAX's compile events by stage, and the
compile plane's own counters at the end of set-up. For a cold split point
``JAX_COMPILATION_CACHE_DIR`` at an empty directory; the next run with the same
directory is the warm one.
"""

import time

T_START = time.perf_counter()       # as run.py: set-up is counted from here

import argparse                                         # noqa: E402
import json                                             # noqa: E402
import os                                               # noqa: E402
import sys                                              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def family(name):
    """``{"name{label=value,...}": number}`` of one registry family."""
    from analytics_zoo_tpu.obs import REGISTRY
    return {k: v for k, v in REGISTRY.snapshot().items()
            if k.startswith(name + "{")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--tag", default="run")
    args = ap.parse_args(argv)

    from harness import runner, spec
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"setup_split.py: {args.workload} needs {cell.chips} TPU "
              f"chip(s), found {len(devices)} on {devices[0].platform!r}",
              file=sys.stderr)
        return 3
    out = cell.load("driver").run(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    line, code = runner.assemble(cell, out, bool(args.trace))
    split = {
        "workload": args.workload, "seed": args.seed, "tag": args.tag,
        "traced": bool(args.trace), "correct": line["correct"],
        "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "zoo_trace": os.environ.get("ZOO_TRACE"),
        "end_to_end": out["end_to_end"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "compile_facts": out["facts"].get("compile"),
        "stage_seconds": family("zoo_setup_seconds_total"),
        "stage_events": family("zoo_setup_events_total"),
        "jax_compile_seconds": family("zoo_jax_compile_seconds_total"),
        "jax_compile_events": family("zoo_jax_compile_events_total"),
    }
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    path = os.path.join(dest, f"setup_split_{args.workload}_{args.tag}.json")
    with open(path, "w") as f:
        json.dump(split, f, indent=1)
    print("setup_split " + json.dumps(
        {k: split[k] for k in ("workload", "tag", "end_to_end")}
        | {"metrics": {k: v for k, v in split["metrics"].items()
                       if k.endswith("_s")},
           "stage_seconds": split["stage_seconds"]}), file=sys.stderr)
    runner.print_result(line)
    return code


if __name__ == "__main__":
    sys.exit(main())
