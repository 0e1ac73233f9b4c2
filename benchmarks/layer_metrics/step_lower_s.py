"""Seconds of set-up under the stage ``compile.lower``, its self seconds in
``zoo_setup_seconds_total{stage}``: each signature's first lowering, which is
the step traced and lowered to MLIR (``_fresh_jit().lower``: nearly all of it,
22.2 s of the MLA cell's 22.3; my chip runs, PR 43), its StableHLO rendered as
text (0.8-1.9 MB: the hundreds of MB are the executable, not the text), the
text hashed into the cache's key, and the lint's pass over it. A warm start
pays it like a cold one: the key is the program itself.

A process total read when the run ends: set-up's alone, because no signature
is first lowered after set-up (``compiles_in_window`` is held at 0;
``setup_program_s.py`` says the rest). 0 where the program counts stages and
this one never ran; nothing to read where it has no such family."""

import os

from harness import spec

STAGE = "compile.lower"
setup = spec.load_py(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "setup_program_s.py"))


def read(ctx):
    return setup.seconds_of(ctx, (STAGE,))
