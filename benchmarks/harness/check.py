"""The comparison that decides `correct` for a training cell.

Numbers compared (each with a limit of its own, kept in the cell's file under
``cells/``), between the program's first steps and the plain reference's:

* ``loss_gap_<k>``: |loss - reference| / |reference| at step k;
* ``grad_gap_big``, ``grad_gap_median``: over the leaves of the first
  gradient, the gap between the program's norm and the reference's norm of a
  leaf, measured against the reference's norm of that leaf or of the median
  leaf, whichever is larger: the worst over the leaves of 4096 elements or
  more, and the median over all leaves;
* ``dparam_gap_big``, ``dparam_gap_median``: the same over every leaf's change
  after the last step, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone);
* ``grad_diff_median``, ``dparam_diff_median``: the median over the leaves of
  the norm of the *difference* between the program's leaf and the reference's,
  measured the same way. A gap of norms is of second order in a random
  rounding error (the norm of a noise-like vector hardly changes when noise is
  added to it), so it sees a batch left out and does not see a lower
  precision; the distance is of first order and is what the control fails;
* ``infeed_bad_rows``: rows of the fed batches that are not a crop (flipped or
  not) of a data-set image carrying the batch's label, or that repeat an image
  within an epoch; an exact comparison, limit 0.

``grad_gap_all`` and ``dparam_gap_all`` (the worst over all leaves) are worked
out and printed, and not compared: on every seed they read 0.2-0.3 for the
program and the same for the control, set by a few leaves of 64-256 elements
(early BatchNorm scales and biases, each a sum over 800 thousand positions
that all but cancels), so they separate nothing (PERF.md has the readings).
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

BIG_LEAF = 4096          # elements


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              leave_out=()) -> Dict[str, float]:
    """Per leaf, |got - want| over max(want, median want)."""
    med = statistics.median(want.values())
    out = {}
    for k, w in want.items():
        if k in leave_out:
            continue
        gap = abs(got[k] - w) / max(w, med, 1e-30)
        out[k] = gap if math.isfinite(gap) else math.inf
    return out


def negligible_leaves(ref_grad_norm: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad_norm.values())
    return [k for k, v in ref_grad_norm.items() if v < 1e-3 * med]


def _summaries(prefix: str, gaps: Dict[str, float], sizes: Dict[str, int],
               out: Dict) -> None:
    big = {k: v for k, v in gaps.items() if sizes[k] >= BIG_LEAF}
    worst = max(gaps, key=gaps.get)
    out[f"{prefix}_all"], out[f"{prefix}_all_leaf"] = gaps[worst], worst
    out[f"{prefix}_median"] = statistics.median(gaps.values())
    if big:
        out[f"{prefix}_big"] = max(big.values())


def compare(program: Dict, reference: Dict, sizes: Dict[str, int]
            ) -> Dict[str, float]:
    """The numbers compared, from the two sides' readings (``losses``,
    ``grad1_norm``, ``dparam_norm`` as reference/train.py returns them) and
    the number of elements of every leaf."""
    out = {}
    for k, (a, b) in enumerate(zip(program["losses"], reference["losses"]), 1):
        gap = abs(a - b) / abs(b)
        out[f"loss_gap_{k}"] = gap if math.isfinite(gap) else math.inf
    _summaries("grad_gap", leaf_gaps(program["grad1_norm"],
                                     reference["grad1_norm"]), sizes, out)
    still = negligible_leaves(reference["grad1_norm"])
    _summaries("dparam_gap", leaf_gaps(
        program["dparam_norm"], reference["dparam_norm"], leave_out=still),
        sizes, out)
    for name, norm in (("grad", "grad1_norm"), ("dparam", "dparam_norm")):
        diff = program.get(f"{name}_diff")
        if diff is None:
            continue
        want = reference[norm]
        med = statistics.median(want.values())
        rel = [diff[k] / max(w, med, 1e-30) for k, w in want.items()
               if not (name == "dparam" and k in still)]
        rel = [v if math.isfinite(v) else math.inf for v in rel]
        out[f"{name}_diff_median"] = statistics.median(rel)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and, for the result line, each number beside its limit.
    A number with no limit on record is an error: no limit is ever guessed."""
    table, ok = {}, True
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"the cell's file sets a limit for {name!r}, "
                           f"which this run did not compare")
        v = numbers[name]
        table[name] = {"value": v, "limit": limit}
        if not (v <= limit):       # NaN fails
            ok = False
    return ok, table
