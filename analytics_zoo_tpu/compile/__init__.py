"""Compile plane — process-wide ownership of every jitted/AOT executable.

The reference platform amortizes graph construction across a cluster once
per job (SURVEY.md §3.2: the Spark driver broadcasts ONE serialized graph);
the TPU rebuild used to pay XLA compilation *per object* — every
``TrainEngine`` called ``jax.jit`` privately, every AutoML trial baked its
hyperparameters into the traced step, and every serving worker or process
restart recompiled from nothing. On real TPU pods compilation is minutes
per executable (cf. arXiv:1909.09756, where startup/compile amortization
is a first-class concern), which dominates exactly the fleet/AutoML/
serving scenarios the north star cares about.

This package centralizes compilation:

* :class:`ExecutableCache` — a process-wide store of AOT-compiled XLA
  executables, keyed by the **lowered program itself** (StableHLO hash +
  device assignment + donation + jax version). The lowering *is* the
  structural fingerprint: flax module tree, input avals, mesh shape/axes,
  optimizer structure, gradient-clip constants and scan fuse-k all land in
  the lowered text, so two engines share an executable exactly when XLA
  would compile the same program — no heuristic keying, no wrong sharing.
* **Hyperparameters-as-arguments** (``orca.learn.optimizers``): scalar
  learning rates route through ``optax.inject_hyperparams`` so they live
  in ``opt_state`` (a traced argument) instead of being baked constants —
  an entire ASHA rung of scalar-hyperparam trials compiles once.
* **Persistence**: executables serialize to disk via
  ``jax.experimental.serialize_executable`` beside JAX's own persistent
  compilation cache, in ONE directory: ``JAX_COMPILATION_CACHE_DIR`` where
  it is set, else ``init_orca_context(compile_cache_dir=...)`` /
  ``ZOO_COMPILE_CACHE``, else — on an accelerator only — the fixed
  ``.zoo_compile_cache/`` at the checkout root
  (:func:`configure_compile_cache`). Warm restarts of ``chip_smoke.py``,
  ``bench.py``, serving workers and resumed studies skip compilation.
* :func:`compile_stats` — counters (compiles, cache/disk hits, compile
  seconds, estimated seconds saved) surfaced through
  ``data_pipeline_stats()``, serving ``/metrics`` and ``bench.py``.
"""

from .cache import (DEFAULT_CACHE_DIR, CachedFunction, ExecutableCache,
                    compile_stats, configure_compile_cache, get_compile_cache,
                    reset_compile_cache, resolve_cache)
from .stats import CompileStats

__all__ = [
    "DEFAULT_CACHE_DIR", "CachedFunction", "CompileStats", "ExecutableCache",
    "compile_stats",
    "configure_compile_cache", "get_compile_cache", "reset_compile_cache",
    "resolve_cache",
]
