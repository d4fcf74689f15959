"""The comparison that decides `correct` for a training cell.

The program and the reference each give, for the first three steps on the
same batches from the same seed's weights: every step's loss, every leaf's
first gradient (the program's as its optimizer holds it: the momentum
after one step from zero is that gradient), and every leaf's change over
the three steps.  Four numbers are compared, each with its limit from the
cell's traffic file:

  loss1_gap   |loss - loss_ref| / |loss_ref| of the first step
  loss_gap    the same, the largest over the steps
  grad_gap    max over leaves of | |g| - |g_ref| | / max(|g_ref|, median)
  change_gap  the same of each leaf's change over the three steps

where |.| is a leaf's norm and `median` the reference's median leaf norm,
since some leaves' gradients are all but zero.  `change_gap` leaves out
leaves whose reference gradient is under a thousandth of the median
leaf's: such a leaf moves by round-off alone.  The first step's loss is
compared on its own because it is the steadiest reading: from the second
step on, the weights carry the gradients' rounding, and the loss gap grows
step by step.
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss1_gap", "loss_gap", "grad_gap", "change_gap")
NEGLIGIBLE = 1e-3


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    if set(prog) != set(ref):
        return math.inf
    med = statistics.median(ref.values())
    keys = [k for k in ref if keep is None or keep(k)]
    return max((abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
                for k in keys), default=math.inf)


def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers; inf where a reading is missing or not
    finite, so that it fails every limit."""
    if len(prog["losses"]) != len(ref["losses"]):
        steps = [math.inf]
    else:
        steps = [abs(a - b) / abs(b)
                 for a, b in zip(prog["losses"], ref["losses"])]
    gmed = statistics.median(ref["grad"].values())
    out = {"loss1_gap": steps[0], "loss_gap": max(steps),
           "grad_gap": leaf_gap(prog["grad"], ref["grad"]),
           "change_gap": leaf_gap(
               prog["change"], ref["change"],
               keep=lambda k: ref["grad"][k] >= NEGLIGIBLE * gmed)}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number within its limit; a null limit marks a number that
    is reported but not compared (it has no upper reading to hold it
    against, see PERF.md)."""
    return all(limits[k] is None or numbers[k] <= limits[k]
               for k in NUMBERS)


def report(numbers: dict, limits: dict) -> dict:
    """The result line's `check` entry: each number beside its limit (a
    missing or non-finite reading shows as 1e300, which JSON can hold)."""
    return {k: {"value": min(numbers[k], 1e300), "limit": limits[k]}
            for k in NUMBERS}
