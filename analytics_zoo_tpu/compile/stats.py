"""Compile telemetry counters.

One :class:`CompileStats` per :class:`~.cache.ExecutableCache`; the global
cache's instance backs :func:`~.cache.compile_stats`, which bench.py prints
per workload and ``data_pipeline_stats()`` / serving ``/metrics`` embed.
"""

from __future__ import annotations

import threading
from typing import Dict

__all__ = ["CompileStats"]


class CompileStats:
    """Monotonic counters for the compile plane, total and per label
    (``train``/``train_multi``/``eval``/``eval_multi``/``predict``/
    ``serving``/...).

    * ``compiles`` / ``compile_s`` — real XLA compilations and their wall
      seconds (lower+compile, the cost a cache hit avoids).
    * ``cache_hits`` / ``disk_hits`` — executables reused from the
      in-process store / loaded from the disk cache. Hits are only counted
      across *distinct* call sites (a function re-finding its own
      executable is ordinary jit behavior, not a save).
    * ``saved_s`` — estimated compile seconds avoided: the recorded
      compile cost of the entry for memory hits, cost minus load time for
      disk hits.
    * ``fallbacks`` — times the plane degraded to plain ``jax.jit``
      (unloadable serialization, aval/sharding mismatch, lowering failure).
    * ``lower_s`` / ``load_s`` — what a warm start still pays: the seconds
      a signature's first lowering took (trace, StableHLO text, key hash,
      lint; paid whether the executable is then compiled, shared or
      loaded) and the seconds disk hits took to unpickle and load. Fed at
      the ``compile.lower`` / ``compile.load`` stages' own boundaries.
    """

    _FIELDS = ("compiles", "cache_hits", "disk_hits", "fallbacks",
               "compile_s", "saved_s", "lower_s", "load_s")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self._total = {f: 0.0 if f.endswith("_s") else 0
                           for f in self._FIELDS}
            self._by_label: Dict[str, Dict] = {}

    def _bucket(self, label: str) -> Dict:
        b = self._by_label.get(label)
        if b is None:
            b = {f: 0.0 if f.endswith("_s") else 0 for f in self._FIELDS}
            self._by_label[label] = b
        return b

    def _add(self, label: str, field: str, amount=1):
        with self._lock:
            self._total[field] += amount
            self._bucket(label or "?")[field] += amount

    def record_compile(self, label: str, seconds: float):
        with self._lock:
            self._total["compiles"] += 1
            self._total["compile_s"] += seconds
            b = self._bucket(label or "?")
            b["compiles"] += 1
            b["compile_s"] += seconds

    def record_hit(self, label: str, saved_s: float = 0.0):
        with self._lock:
            self._total["cache_hits"] += 1
            self._total["saved_s"] += saved_s
            b = self._bucket(label or "?")
            b["cache_hits"] += 1
            b["saved_s"] += saved_s

    def record_disk_hit(self, label: str, saved_s: float = 0.0):
        with self._lock:
            self._total["disk_hits"] += 1
            self._total["saved_s"] += max(saved_s, 0.0)
            b = self._bucket(label or "?")
            b["disk_hits"] += 1
            b["saved_s"] += max(saved_s, 0.0)

    def record_fallback(self, label: str):
        self._add(label, "fallbacks")

    def record_lower(self, label: str, seconds: float):
        self._add(label, "lower_s", seconds)

    def record_load(self, label: str, seconds: float):
        self._add(label, "load_s", seconds)

    def counts(self, label: str) -> Dict:
        """Counters for one label (zeros when the label never compiled)."""
        with self._lock:
            b = self._by_label.get(label)
            return dict(b) if b else {f: 0.0 if f.endswith("_s") else 0
                                      for f in self._FIELDS}

    def snapshot(self) -> Dict:
        with self._lock:
            out = {f: (round(v, 6) if isinstance(v, float) else v)
                   for f, v in self._total.items()}
            out["by_label"] = {
                lbl: {f: (round(v, 6) if isinstance(v, float) else v)
                      for f, v in b.items()}
                for lbl, b in sorted(self._by_label.items())}
            return out

    def delta_since(self, baseline: Dict) -> Dict:
        """Counters accrued since ``baseline`` (an earlier ``snapshot()``).
        Lets a consumer sharing the process-wide cache (a study, one bench
        workload) attribute ONLY its own compiles/hits — the cumulative
        snapshot would claim everything the process ever compiled."""
        now = self.snapshot()
        base_labels = baseline.get("by_label", {})
        out = {f: round(now[f] - baseline.get(f, 0), 6)
               for f in self._FIELDS}
        out["by_label"] = {}
        for lbl, b in now["by_label"].items():
            base = base_labels.get(lbl, {})
            d = {f: round(b[f] - base.get(f, 0), 6) for f in self._FIELDS}
            if any(d.values()):
                out["by_label"][lbl] = d
        return out
