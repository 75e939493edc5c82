"""The comparison that decides ``correct``.

A training run is compared with the float32 reference over its first
three iterations: the first step's loss, the norm of the first gradient as
the optimizer got it (the momentum after one step, which starts at zero),
and the norm of the masters' change after three steps. The losses of the
second and third steps are not compared: there the bf16 working copy has
drifted from the float32 trajectory (on the chip their gaps read up to 45
times the first step's on a sound run, and less than three times below
the control's). Norms are compared leaf by leaf, the gap between the
program's norm and the reference's taken against the larger of the
reference's norm of that leaf and of the median leaf, and the worst leaf
counts. A leaf whose reference gradient is below
a thousandth of the median leaf's (a key bias under softmax, whose
gradient is zero up to rounding) is left out of both norms. The active
worker counts of the window's iterations are compared exactly.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

#: a leaf whose reference gradient norm is below this share of the median
#: leaf's moves by rounding alone and is not compared
NEGLIGIBLE = 1e-3

#: the numbers compared, in the order they print
NAMES = ("loss_gap", "grad_gap", "change_gap", "y_mismatch")


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep) -> Dict[str, float]:
    scale = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], scale) for k in keep}


def compared_leaves(ref) -> list:
    med = float(np.median(list(ref.grad_norms.values())))
    return [k for k, v in ref.grad_norms.items() if v >= NEGLIGIBLE * med]


def gaps(prog, ref) -> Dict[str, float]:
    """``prog``, ``ref``: `reference.common.Readings` of the program and of
    the reference (or of the control or a fault put in the program's
    place)."""
    if set(prog.grad_norms) != set(ref.grad_norms):
        raise ValueError("the program's leaves differ from the reference's")
    keep = compared_leaves(ref)
    return {"loss_gap": float(abs(prog.losses[0] - ref.losses[0])
                              / abs(ref.losses[0])),
            "grad_gap": max(leaf_gaps(prog.grad_norms, ref.grad_norms,
                                      keep).values()),
            "change_gap": max(leaf_gaps(prog.change_norms,
                                        ref.change_norms, keep).values())}


def y_mismatch(run) -> int:
    """Iterations whose active-worker count differs from what the mix's
    prices and bids give, in the set-up's three and the window's, plus
    any difference in how many iterations the window ran."""
    n = abs(len(run.y_window) - len(run.expected_y))
    m = min(len(run.y_window), len(run.expected_y))
    n += int(np.sum(run.y_window[:m] != run.expected_y[:m]))
    return n + int(np.sum(run.first_y != run.expected_first_y))


def decide(numbers: Dict[str, float], limits: Optional[Dict]):
    """(correct, {name: {"value", "limit"}}). Without limits (a cell not
    yet calibrated) nothing is correct. A limits file names every number:
    one it sets to null has no upper reading on the chip (neither the
    control nor a fault reads far enough above the program) and is not
    compared; one it leaves out fails."""
    table = {}
    ok = limits is not None
    for name in NAMES:
        if name not in numbers:
            continue
        if limits is not None and name in limits and limits[name] is None:
            continue
        value = numbers[name]
        limit = None if limits is None else limits.get(name)
        table[name] = {"value": value, "limit": limit}
        ok = ok and limit is not None and np.isfinite(value) \
            and value <= limit
    return bool(ok), table
