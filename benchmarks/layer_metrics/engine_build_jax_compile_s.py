"""The part of ``engine_build_s`` that is JAX compiling, or fetching from its
persistent cache, the programs of eager operations: the seconds of
``zoo_jax_compile_seconds_total{event="backend_compile"}`` whose ``stage`` is
one of the engine's four build stages. The eager ``module.init`` meets every
operation of the model once and each is a program of its own, on every run.

``backend_compile`` alone, not ``cache_retrieval`` beside it: in the installed
JAX (0.9.0) ``backend_compile_duration`` is taken around
``compile_or_get_cached``, so a cache hit's retrieval already lies inside it
(the family's doc string, ``analytics_zoo_tpu/obs/trace.py``; tier-1's
``test_backend_compile_covers_a_persistent_cache_hits_retrieval`` pins it).

A process total read when the run ends: set-up's alone, because the stages it
selects run in set-up only (``setup_program_s.py``); what the reference
compiles afterwards is filed under the stage ``none``. Nothing to read where
the program has no such family."""

import os

from harness import spec

FAMILY = "zoo_jax_compile_seconds_total"
here = os.path.dirname(os.path.abspath(__file__))
setup = spec.load_py(os.path.join(here, "setup_program_s.py"))
build = spec.load_py(os.path.join(here, "engine_build_s.py"))


def read(ctx):
    fam = setup.family(ctx, FAMILY)
    if fam is None or setup.stage_seconds(ctx) is None:
        return None
    return sum(float(child.value) for labels, child in fam.samples()
               if labels["event"] == "backend_compile"
               and labels["stage"] in build.STAGES)
