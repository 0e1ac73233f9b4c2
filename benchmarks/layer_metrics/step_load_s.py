"""Seconds of set-up under the stage ``compile.load``, its self seconds in
``zoo_setup_seconds_total{stage}``: executables read back from the disk cache
(``pickle.load`` and ``deserialize_and_load`` in ``compile/cache.py
_load_disk``). 0 in a run that compiled its step: ``compile_s`` holds that
run's cost.

A process total read when the run ends: set-up's alone, because no executable
is loaded after set-up (``compiles_in_window`` is held at 0;
``setup_program_s.py`` says the rest). Nothing to read where the program has
no such family."""

import os

from harness import spec

STAGE = "compile.load"
setup = spec.load_py(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "setup_program_s.py"))


def read(ctx):
    return setup.seconds_of(ctx, (STAGE,))
