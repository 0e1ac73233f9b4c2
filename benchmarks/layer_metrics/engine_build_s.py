"""Seconds of set-up in the engine's ``build``: the self seconds of the
stages ``engine.build`` (the sample cut, the prologue), ``engine.init_vars``
(the module's eager ``init``), ``engine.place_params`` (partition specs, the
sharding plan, the parameters and other collections put on the device) and
``engine.opt_init`` (``tx.init`` and its placement), from
``zoo_setup_seconds_total{stage}``.

A process total read when the run ends: set-up's alone, because an engine is
built once and nothing is built after set-up (``setup_program_s.py`` says
why). Nothing to read where the program has no such family."""

import os

from harness import spec

STAGES = ("engine.build", "engine.init_vars", "engine.place_params",
          "engine.opt_init")
setup = spec.load_py(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "setup_program_s.py"))


def read(ctx):
    return setup.seconds_of(ctx, STAGES)
