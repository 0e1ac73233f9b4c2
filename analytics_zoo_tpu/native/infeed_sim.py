"""Pump-vs-direct infeed crossover simulation.

The InfeedPump's design claim — "background device_put overlaps compute and
e2e approaches the compute rate" — can be checked without hardware as far
as the host side goes. This harness does that: device_put is modelled as a GIL-releasing sleep of
``nbytes / bandwidth + latency`` (exactly how a DMA transfer behaves from
the host thread's perspective) and the train step as a GIL-releasing sleep
of the compute time (XLA dispatch releases the GIL the same way). The
pump path runs the REAL InfeedPump (native queue + producer thread); the
direct path calls the same fake device_put inline.

What it shows (see scripts/infeed_crossover.py for the sweep): with
PCIe/DMA-class bandwidth the pumped steady-state step time collapses to
~max(compute, transfer) while direct stays at compute + transfer — i.e.
e2e approaches the compute rate exactly when transfer < compute, which
holds for ResNet-50-class batches (38 MB) at >= 1 GB/s. At a link of tens
of MB/s both paths are transfer-bound and overlap cannot help. What the
real link does is chip_smoke.py's H2D observation, not this model.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from .infeed import InfeedPump


def _busy_free_sleep(seconds: float):
    # time.sleep releases the GIL — the same overlap behavior as a DMA
    # transfer or XLA execution awaited from another thread
    if seconds > 0:
        time.sleep(seconds)


class FakeDevice:
    """Models host->device transfer at ``bandwidth_gbps`` (decimal GB/s)
    with a fixed per-call ``latency_s``, and a compute step of
    ``step_time_s``."""

    def __init__(self, bandwidth_gbps: float, step_time_s: float,
                 latency_s: float = 200e-6):
        self.bandwidth = bandwidth_gbps * 1e9
        self.latency = latency_s
        self.step_time = step_time_s

    def device_put(self, batch) -> object:
        if isinstance(batch, np.ndarray):
            nbytes = batch.nbytes
        else:
            nbytes = sum(a.nbytes for a in batch)
        _busy_free_sleep(self.latency + nbytes / self.bandwidth)
        return batch

    def train_step(self, dev_batch):
        _busy_free_sleep(self.step_time)


def measure(device: FakeDevice, batches: List, steps: int,
            use_pump: bool) -> float:
    """Steady-state seconds/step over ``steps`` batches."""
    def factory():
        for i in range(steps):
            yield batches[i % len(batches)]

    t0 = time.perf_counter()
    if use_pump:
        # lanes=1: the FakeDevice models ONE DMA link as a sleep, so
        # concurrent lane sleeps would simulate a doubled link, not
        # overlapped transfers on the same link
        for dev_batch in InfeedPump(factory, device_put=device.device_put,
                                    lanes=1, max_lanes=1):
            device.train_step(dev_batch)
    else:
        for batch in factory():
            device.train_step(device.device_put(batch))
    return (time.perf_counter() - t0) / steps


def simulate_crossover(batch_mb: float = 38.5, step_time_ms: float = 100.0,
                       bandwidths_gbps=(0.01, 0.05, 0.25, 1.0, 4.0, 16.0),
                       steps: int = 30) -> Dict[float, Dict[str, float]]:
    """Sweep bandwidths; returns per-bandwidth direct/pumped seconds/step
    plus the ideal overlap bound max(compute, transfer)."""
    n = int(batch_mb * 1e6)
    batches = [np.zeros(n, np.uint8) for _ in range(3)]
    out = {}
    for bw in bandwidths_gbps:
        dev = FakeDevice(bw, step_time_ms / 1e3)
        transfer = n / (bw * 1e9)
        direct = measure(dev, batches, steps, use_pump=False)
        pumped = measure(dev, batches, steps, use_pump=True)
        out[bw] = {
            "transfer_s": transfer,
            "direct_s_per_step": direct,
            "pumped_s_per_step": pumped,
            "ideal_overlap_s": max(step_time_ms / 1e3, transfer),
            "pump_speedup": direct / pumped,
        }
    return out
