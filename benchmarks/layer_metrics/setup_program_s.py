"""Seconds of set-up spent inside the program's own set-up stages
(``analytics_zoo_tpu/obs/trace.py stage``): the sum of every stage's self
seconds in ``zoo_setup_seconds_total{stage}`` but ``fit.fuse_probe``'s. Self
times add up to the union of the stages, so nothing is counted twice where
``compile.*`` nests inside ``compile.first_call``. ``setup_s`` less this
number is the harness's own share (imports, the data and the seeded weights
made on the host, the weights put in place, the readings between the steps).

A process total, read when the run ends. That is set-up's alone because
nothing is built, lowered or loaded after set-up: ``compiles_in_window`` is
held at 0, the benchmark's feed has no ``supports_fused`` (no probe runs), and
the reference opens no stage. Counted with tracing armed or not, so the
profiler session of a traced run, which opens after set-up, stretches nothing
here. Where the program has no such family (before PR 43) there is nothing to
read.

``ctx["registry"]``, where a test gives it, stands in for the program's
registry. The other set-up readers take ``seconds_of`` from this file.
"""

FAMILY = "zoo_setup_seconds_total"
NOT_SETUP = ("fit.fuse_probe",)


def family(ctx, name):
    """The registry's family of that name, or None."""
    registry = ctx.get("registry")
    if registry is None:
        from analytics_zoo_tpu.obs import REGISTRY as registry
    return next((f for f in registry.families() if f.name == name), None)


def stage_seconds(ctx):
    """``{stage: self seconds}``, or None where no stage was ever counted."""
    fam = family(ctx, FAMILY)
    if fam is None:
        return None
    found = {labels["stage"]: float(child.value)
             for labels, child in fam.samples()}
    return found or None


def seconds_of(ctx, stages):
    """Self seconds of the named stages together (one that never ran adds
    0), or None where no stage was ever counted."""
    by_stage = stage_seconds(ctx)
    if by_stage is None:
        return None
    return sum(by_stage.get(stage, 0.0) for stage in stages)


def read(ctx):
    by_stage = stage_seconds(ctx)
    if by_stage is None:
        return None
    return sum(s for stage, s in by_stage.items() if stage not in NOT_SETUP)
