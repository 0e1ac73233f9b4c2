"""Static-analysis plane: lint what the other planes only assert.

Four parts (ISSUE 9):

* :mod:`.hlo_lint` — a linter over lowered StableHLO, hooked into the
  compile plane (every ``ExecutableCache`` lowering is linted before it
  compiles): f64 reaching a TPU program, 64-bit dtype promotion, large
  undonated inputs in donating programs, host callbacks inside train
  steps, and per-mesh-axis collective launch/byte counts measured from
  the module and cross-checked against the engine's declared accounting.
* :mod:`.golden` — program-contract snapshots (collective launches by
  mesh axis, gather bytes, donation set, executable count) for the train
  step, committed under ``tests/goldens/`` and diffed in CI.
* :mod:`.races` — a runtime race detector: traced-lock instrumentation
  building a lock-order graph (inversion = deadlock risk) plus watched
  shared objects whose attributes are written from >=2 threads without
  their registered lock.
* :mod:`.repolint` — AST-based repo rules behind the ``zoo-lint`` CLI
  (unregistered ``ZOO_*`` env reads, silent ``except: pass``, threads
  without daemon/name, mutable default args), run as a CI gate.
"""

from .hlo_lint import (HloLinter, HloLintError, LintFinding,
                       declare_accounting, lint_report, on_lowering,
                       parse_collectives)
from .races import RaceDetector, get_detector

__all__ = ["HloLinter", "HloLintError", "LintFinding", "RaceDetector",
           "declare_accounting", "get_detector", "lint_report", "on_lowering",
           "parse_collectives"]
