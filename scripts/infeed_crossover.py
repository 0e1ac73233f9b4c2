#!/usr/bin/env python
"""Pump-vs-direct infeed crossover sweep (round-4 verdict item 7).

Runs the REAL InfeedPump against a modelled device (native/infeed_sim.py)
across host->device bandwidths from a degraded link (10 MB/s) to PCIe/DMA
class (16 GB/s) with a ResNet-50-sized batch (256 x 224 x 224 x 3 uint8 =
38.5 MB) and a 100 ms compute step (~2560 img/s). Prints the measured
steady-state step times and writes docs-ready JSON.

Usage: python scripts/infeed_crossover.py [--steps 30]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-mb", type=float, default=38.5)
    ap.add_argument("--step-ms", type=float, default=100.0)
    args = ap.parse_args()

    from analytics_zoo_tpu.native.infeed_sim import simulate_crossover
    res = simulate_crossover(batch_mb=args.batch_mb,
                             step_time_ms=args.step_ms, steps=args.steps)
    print(f"{'GB/s':>7} {'transfer':>9} {'direct':>9} {'pumped':>9} "
          f"{'ideal':>9} {'speedup':>8}")
    for bw, r in res.items():
        print(f"{bw:>7} {r['transfer_s']*1e3:>8.1f}m "
              f"{r['direct_s_per_step']*1e3:>8.1f}m "
              f"{r['pumped_s_per_step']*1e3:>8.1f}m "
              f"{r['ideal_overlap_s']*1e3:>8.1f}m "
              f"{r['pump_speedup']:>8.2f}")
    print(json.dumps({str(k): v for k, v in res.items()}))


if __name__ == "__main__":
    main()
