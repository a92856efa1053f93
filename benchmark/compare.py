"""The comparison that decides ``correct`` for a training cell.

Both sides hand over the same readings of the first steps: each step's
loss, the norm of every leaf's first gradient as the optimizer got it,
and the norm of every leaf's change over those steps.  A leaf's gap is
the distance between the program's norm and the reference's (not the
norm of their difference), measured against the reference's norm of that
leaf or of the median leaf, whichever is larger: some gradients are all
but zero.  Gradients and changes are each judged by their worst leaf
(``grad_gap``, ``delta_gap``); the median leaf's gap is taken beside it
(``grad_gap_median``, ``delta_gap_median``).  Which of the numbers a cell
is held to is what its limits file names.

Leaves whose first gradient is nought to rounding in the reference
(under a thousandth of the median leaf's) move under Adam by round-off
alone, so they are left out of the change; the rule is on the reference's
gradient, never on a leaf's name.

Each number has a limit of its own, in ``limits/<cell>.json`` with the
readings it was set from.
"""

from __future__ import annotations

import json
import math
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
DEAD_GRADIENT = 1e-3          # of the median leaf's first-gradient norm


def load_limits(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def leaf_gaps(program: dict, reference: dict, leaves) -> dict:
    """Every leaf's gap of norms, against the larger of the reference's
    norm of that leaf and of the median leaf."""
    floor = statistics.median(reference.values())
    out = {}
    for name in leaves:
        got, want = program[name], reference[name]
        scale = max(want, floor)
        gap = abs(got - want) / scale if scale > 0 else (
            0.0 if got == want else math.inf)
        out[name] = gap if math.isfinite(gap) else math.inf
    return out


def _worst_and_median(program: dict, reference: dict, leaves) -> tuple:
    per_leaf = leaf_gaps(program, reference, leaves)
    where = max(per_leaf, key=per_leaf.get)
    return per_leaf[where], where, statistics.median(per_leaf.values())


def gaps(program: dict, reference: dict) -> tuple:
    """(numbers compared, the leaf each worst-leaf number came from)."""
    if set(program["grad_norms"]) != set(reference["grad_norms"]):
        raise ValueError("the program and the reference name different "
                         "leaves")
    out, where = {}, {}
    for i, (got, want) in enumerate(zip(program["losses"],
                                        reference["losses"]), 1):
        gap = abs(got - want) / abs(want)
        out[f"loss{i}_gap"] = gap if math.isfinite(gap) else math.inf
    grads = reference["grad_norms"]
    out["grad_gap"], where["grad_gap"], out["grad_gap_median"] = \
        _worst_and_median(program["grad_norms"], grads, grads)
    floor = DEAD_GRADIENT * statistics.median(grads.values())
    live = [name for name, norm in grads.items() if norm >= floor]
    out["delta_gap"], where["delta_gap"], out["delta_gap_median"] = \
        _worst_and_median(program["delta_norms"], reference["delta_norms"],
                          live)
    return out, where


def decide(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number the limits
    name has to be there and at or under its limit."""
    compared = {name: {"value": numbers.get(name, math.inf), "limit": limit}
                for name, limit in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return correct, compared
