"""The comparison that decides ``correct`` for a training cell.

The program's first three steps, taken through the window's own loop, are
set against the plain reference's three steps from the same weights and
batches. Three numbers are compared, each with its limit from the cell's
file under ``workloads/``:

- ``loss_gap``: the largest gap between the program's and the reference's
  loss over the three steps, in nats.
- ``grad_gap``: the first gradient as the optimizer gets it (after the
  global-norm clip), by the worst leaf: the gap between the two leaf norms
  over the reference's norm of that leaf or of the median leaf, whichever is
  larger.
- ``change_gap``: the same measure for the parameters' change over the
  three steps. Leaves whose reference gradient is under a thousandth of
  the median leaf's are left out: Adam moves them by round-off alone.
"""

from __future__ import annotations

import statistics

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
# A leaf with a reference gradient under this share of the median leaf's
# moves under Adam by round-off alone, and is left out of change_gap.
ZERO_GRAD_SHARE = 1e-3


def worst_leaf(prog: dict, ref: dict, keys) -> tuple[float, str]:
    keys = list(keys)
    med = statistics.median(ref[k] for k in keys)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``losses`` (per step), ``grad_norms`` and
    ``change_norms`` (leaf name -> norm)."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("program and reference have different leaves: "
                         f"{sorted(prog['grad_norms'])} vs "
                         f"{sorted(ref['grad_norms'])}")
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"],
                                              strict=True))
    grad_gap, grad_leaf = worst_leaf(prog["grad_norms"], ref["grad_norms"],
                                     ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    moved = [k for k, g in ref["grad_norms"].items()
             if g >= ZERO_GRAD_SHARE * med]
    change_gap, change_leaf = worst_leaf(prog["change_norms"],
                                         ref["change_norms"], moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap, "grad_leaf": grad_leaf,
            "change_leaf": change_leaf,
            "left_out": sorted(set(ref["grad_norms"]) - set(moved))}


def judge(read: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when every one is at most its
    limit (a number that is not finite fails)."""
    checks = {}
    ok = True
    for name in NUMBERS:
        v, lim = float(read[name]), float(limits[name])
        checks[name] = {"value": v, "limit": lim}
        ok &= v <= lim          # NaN compares false
    return ok, checks
