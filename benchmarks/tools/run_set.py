#!/usr/bin/env python3
"""Run a cell several times, each run a new process of ``run.py`` (this parent
never touches JAX, so the chip is the child's), keep every result line, and
print each metric's spread as the contract reads it: the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a share of
the median.

    chiprun -- python benchmarks/tools/run_set.py --workload <cell> \
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 20 \
        --out chiprun_out/set_<cell>.json [--traced-seeds 21,22,23]
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

TOOLS = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(TOOLS), "run.py")


def one(workload, seed, seconds, trace):
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = p.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        line = None
    return {"seed": seed, "trace": trace, "rc": p.returncode, "wall_s": wall,
            "line": line, "stderr_tail": p.stderr[-1500:]}


def spread(values):
    if len(values) < 3:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    runs = []

    def save():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seconds": args.seconds,
                       "runs": runs}, f, indent=1)

    for s in range(args.sets):
        for seed in seeds:
            r = one(args.workload, seed, args.seconds, 0)
            r["set"] = s
            runs.append(r)
            m = (r["line"] or {}).get("metrics", {})
            print(f"set {s} seed {seed} rc {r['rc']} wall {r['wall_s']:.1f} "
                  f"correct {(r['line'] or {}).get('correct')} "
                  + " ".join(f"{k}={v['value']:.4f}" for k, v in m.items()),
                  flush=True)
            if r["rc"] != 0 or not r["line"]:
                print(r["stderr_tail"], flush=True)
            save()
    for seed in [int(s) for s in args.traced_seeds.split(",") if s]:
        r = one(args.workload, seed, args.seconds, 1)
        r["set"] = "traced"
        runs.append(r)
        print(f"traced seed {seed} rc {r['rc']} wall {r['wall_s']:.1f} "
              f"{json.dumps(r['line'])[:3000] if r['line'] else r['stderr_tail']}",
              flush=True)
        save()
    for s in range(args.sets):
        rows = [r["line"] for r in runs if r["set"] == s and r["line"]]
        if not rows:
            continue
        for name in rows[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in rows]
            print(f"set {s} {name}: median {statistics.median(vals):.4f} "
                  f"spread {spread(vals)} first {vals[0]:.4f} "
                  f"min {min(vals):.4f} max {max(vals):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
