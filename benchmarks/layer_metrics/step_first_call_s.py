"""Seconds of set-up under the stage ``compile.first_call``, its self seconds
in ``zoo_setup_seconds_total{stage}``: each signature's first call
(``CachedFunction.__call__`` where it holds no executable yet) LESS the
``compile.*`` stages inside it, so what finding the executable costs beyond
lowering, compiling, loading and saving, and the first execution's enqueue.

A process total read when the run ends: set-up's alone, because no signature
is first called after set-up (``compiles_in_window`` is held at 0;
``setup_program_s.py`` says the rest). 0 where the program counts stages and
this one never ran; nothing to read where it has no such family."""

import os

from harness import spec

STAGE = "compile.first_call"
setup = spec.load_py(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "setup_program_s.py"))


def read(ctx):
    return setup.seconds_of(ctx, (STAGE,))
