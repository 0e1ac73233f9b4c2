#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced, ``breakdown``),
then ``compared``: every number that decided ``correct`` beside its limit.
Refuses any platform but ``tpu`` and fewer chips than the cell asks for: it
exits non-zero and prints no result. Nothing falls back to the CPU.
"""

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse                                         # noqa: E402
import os                                               # noqa: E402
import sys                                              # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))          # the program
sys.path.insert(0, BENCH_DIR)                           # harness, reference


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import runner, spec
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} device(s) on platform "
              f"{devices[0].platform!r}. No result.", file=sys.stderr)
        return 3
    return runner.run_and_print(cell, args.seed, args.seconds,
                                bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
