"""The numbers that decide ``correct``, from two jobs' readings.

Each reading is a per-(learner, leaf) norm.  A gap is taken by the worst
leaf: the distance between the two norms (not the norm of the
difference), over the reference's norm of that leaf or of the median
leaf, whichever is larger, since some gradients are all but zero.

* ``loss_gap``: the largest relative gap of the three steps' losses;
* ``grad_gap``: the first gradient as the optimizer got it;
* ``change_gap``: the parameters' change after three steps, leaving out
  leaves whose reference gradient is under a thousandth of the median
  leaf's (they move by round-off alone).
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
QUIET = 1e-3


def worst_gap(got: dict, want: dict, skip=frozenset()) -> float:
    med = statistics.median(want.values())
    gaps = [abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in want if k not in skip]
    if any(not math.isfinite(got[k]) for k in want):
        return math.inf
    return max(gaps)


def worst(got: dict, want: dict, k: int = 3, skip=frozenset()) -> list:
    """The ``k`` (learner, leaf) keys with the largest gaps, each as
    ``[learner, leaf, got, want, gap]``: what a reading's number is made
    of."""
    med = statistics.median(want.values())
    rows = [[key[0], key[1], got[key], want[key],
             abs(got[key] - want[key]) / max(want[key], med, 1e-30)]
            for key in want if key not in skip]
    return sorted(rows, key=lambda r: -r[4])[:k]


def quiet(want: dict) -> set:
    """Leaves whose reference gradient is under ``QUIET`` of the median
    leaf's."""
    med = statistics.median(want["grad"].values())
    return {k for k, v in want["grad"].items() if v < QUIET * med}


def numbers(got: dict, want: dict) -> dict:
    if set(got["grad"]) != set(want["grad"]):
        raise ValueError("the two jobs' parameter layouts differ")
    loss = max(abs(a - b) / abs(b) if math.isfinite(a) else math.inf
               for a, b in zip(got["losses"], want["losses"]))
    return {"loss_gap": loss,
            "grad_gap": worst_gap(got["grad"], want["grad"]),
            "change_gap": worst_gap(got["change"], want["change"],
                                    quiet(want))}


def checks(nums: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} in a fixed order; every number must
    have a limit."""
    return {k: {"value": nums[k], "limit": limits[k]} for k in NUMBERS}


def passed(chk: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in chk.values())
