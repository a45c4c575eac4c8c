"""The comparison that decides ``correct`` for a training cell.

Three numbers, each a worst case, compare the program's first steps with the
plain reference's (``bench/reference.py``):

* ``loss_gap``  — largest relative gap of a step's node-mean loss;
* ``grad_gap``  — the first gradient as the optimizer holds it after one
  step (``W g'_0``, read back from the quasi-global buffer): the worst leaf's
  gap between the program's norm and the reference's;
* ``delta_gap`` — the parameters' change after the checked steps, the worst
  leaf in the same way.

A leaf's gap is ``|‖p‖ - ‖r‖|`` over the larger of the reference's norm of
that leaf and of the median leaf, so that a leaf whose norm is all but zero
does not decide alone.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by weight decay and round-off alone and
are left out of ``delta_gap``.

Each cell's limits live in ``bench/limits/<cell>.json``, set from readings of
sound runs and of the control and faults (``bench/calibrate.py``).
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "delta_gap")
STILL_LEAF = 1e-3     # reference gradient under this share of the median


def _leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    keys = [k for k in ref if keep is None or k in keep]
    floor = statistics.median(ref[k] for k in keys)
    gaps = {}
    for k in keys:
        gap = abs(prog.get(k, math.nan) - ref[k]) / max(ref[k], floor)
        gaps[k] = gap if math.isfinite(gap) else math.inf
    return gaps


def moving_leaves(raw_grad: dict) -> set:
    """Leaves whose reference gradient is at least STILL_LEAF of the
    median leaf's."""
    med = statistics.median(raw_grad.values())
    return {k for k, v in raw_grad.items() if v >= STILL_LEAF * med}


def _leaves(prog, ref) -> dict:
    return {"grad_gap": _leaf_gaps(prog.first_grad, ref.first_grad),
            "delta_gap": _leaf_gaps(prog.delta, ref.delta,
                                    moving_leaves(ref.raw_grad))}


def numbers(prog, ref) -> dict:
    """The compared numbers for the program's readings against the
    reference's (both ``reference.Readings``)."""
    loss_gap = 0.0
    for p, r in zip(prog.losses, ref.losses, strict=True):
        gap = abs(p - r) / abs(r)
        loss_gap = max(loss_gap, gap) if math.isfinite(gap) else math.inf
    out = {"loss_gap": loss_gap}
    out.update({k: max(g.values()) for k, g in _leaves(prog, ref).items()})
    return out


def worst_leaves(prog, ref) -> list:
    """Lines naming each leaf-wise number's worst leaf, with both norms."""
    lines = []
    names = {"grad_gap": "first_grad", "delta_gap": "delta"}
    for k, gaps in _leaves(prog, ref).items():
        leaf = max(gaps, key=gaps.get)
        p, r = getattr(prog, names[k]), getattr(ref, names[k])
        lines.append(f"{k} worst leaf {leaf}: program {p.get(leaf)!r} "
                     f"reference {r[leaf]!r}")
    lines.append(f"losses: program {prog.losses!r} reference {ref.losses!r}")
    return lines


def verdict(nums: dict, limits: dict) -> bool:
    """Every number finite and at or under its limit."""
    return all(math.isfinite(nums[k]) and nums[k] <= limits[k]
               for k in NUMBERS)
