"""Test fixtures: an 8-device virtual CPU mesh stands in for a TPU slice.

Mirrors the reference's single-machine test strategy (SURVEY.md §4: every
"distributed" test runs on one machine — Spark local mode + local Ray; fixture
at pyzoo/test/zoo/orca/learn/ray/pytorch/conftest.py:22-40). Here the fake
backend is JAX CPU with xla_force_host_platform_device_count=8.
"""

import os

# Force CPU even on a machine with a TPU: the test suite needs the 8-device
# virtual mesh, a chip belongs to one process at a time, and chip_smoke.py
# is what runs there. A plug-in or an earlier import may have fixed
# jax_platforms already, so set it through jax.config as well as the
# environment (which child processes inherit).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Pin full-f32 matmuls suite-wide: numeric-equivalence tests compare against
# float32 references with tight tolerances and must not depend on what a
# backend's default precision happens to be, or on test order.
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402

# Opt-in runtime race detection for the whole run (ISSUE 9 / STATUS row 37):
# ZOO_RACE_DETECT=1 routes every threading.Lock/RLock created from here on
# through the analysis plane's traced wrappers, builds the lock-order graph
# across all tier-1 tests, and prints the report at session end. Enabled
# before the planes construct their locks (ckpt writer, infeed pump,
# watchdog, serving, trial runtime — all built lazily at runtime), but
# note: module-level locks created while the package __init__ chain
# imports (e.g. common/context._lock) predate enable() and stay untraced
# — the detector itself lives inside that package.
_race_detector = None
from analytics_zoo_tpu.common import knobs as _zoo_knobs  # noqa: E402

if _zoo_knobs.get("ZOO_RACE_DETECT"):
    from analytics_zoo_tpu.analysis.races import get_detector

    _race_detector = get_detector()
    _race_detector.enable()


def pytest_sessionfinish(session, exitstatus):
    if _race_detector is None:
        return
    import json

    _race_detector.disable()
    rep = _race_detector.report()
    print("\nRACE_DETECT=" + json.dumps(
        {"locks": rep["locks"], "acquisitions": rep["acquisitions"],
         "order_edges": rep["order_edges"],
         "inversions": rep["inversions"],
         "unsynchronized": rep["unsynchronized"],
         "clean": rep["clean"]}))


@pytest.fixture()
def orca_context():
    # function-scoped but idempotent: reuse the live context when one exists
    # (quietly — init_orca_context would warn), rebuild only after a test
    # (e.g. the fsdp-mesh suite) stopped it. atexit stops the last one.
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.common import context as ctx_mod
    live = ctx_mod._current
    if live is not None and not live._stopped:
        yield live
    else:
        yield init_orca_context("cpu-sim", mesh_axes={"dp": -1})
