"""The observability plane — the first layer that can explain the others.

Three pieces over every plane built in PRs 1–9:

* :mod:`~analytics_zoo_tpu.obs.registry` — the unified typed metrics
  registry (Counter/Gauge/Histogram with label sets) all six existing
  stats surfaces register into, keeping their dict-returning APIs.
* :mod:`~analytics_zoo_tpu.obs.trace` — structured spans with explicit
  cross-thread (and cross-payload, for serving) context propagation:
  one trace id follows ``fit → epoch → step-dispatch → h2d-lane →
  ckpt-writer`` and ``request → decode → batch → device-dispatch →
  respond``. Disarmed cost is one flag check per site (``ZOO_TRACE`` to
  arm).
* :mod:`~analytics_zoo_tpu.obs.export` — Prometheus text exposition
  (serving ``GET /metrics.prom``, ``zoo-metrics dump``) and
  Chrome/Perfetto ``trace_event`` JSON step timelines (``zoo-metrics
  perfetto``, ``ZOO_TRACE_PERFETTO=<path>``).

See ``docs/observability.md`` for the metric naming rules, the span
catalogue and the Perfetto how-to.
"""

from . import trace
from .export import perfetto_trace, prometheus_text, write_perfetto
from .registry import REGISTRY, Counter, Gauge, Histogram, MetricsRegistry


def _compile_plane_snapshot():
    # lazy import: the compile plane is heavier than this package and may
    # itself (transitively) import obs
    from ..compile import compile_stats
    snap = compile_stats()
    snap.pop("by_label", None)      # per-label detail stays on the JSON side
    return snap


# the process-wide compile cache has exactly one stats object — adapt it
# directly (the per-instance planes register themselves at construction)
REGISTRY.register_collector("zoo_compile", _compile_plane_snapshot)

__all__ = ["REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "trace", "prometheus_text", "perfetto_trace", "write_perfetto"]
