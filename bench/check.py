"""The comparison that decides `correct` for a training cell.

Three numbers, each against its limit from the cell file:

* `loss_gap`: the largest relative gap between the program's and the
  reference's loss over the first steps.
* `grad_gap`: for each leaf, the gap between the norms of the program's and
  the reference's clipped step-0 gradient. The norm of their difference is
  not used. The gap is taken over the larger of the reference leaf's norm
  and the median leaf's. The worst leaf counts.
* `change_gap`: the same for the parameters' change over the first steps.
  The median is over the leaves the reference moves.

Leaves whose reference gradient is under a thousandth of the median leaf's
move by round-off alone and are left out of both leaf readings. That rule
reads the reference's gradient and never a leaf's name.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

EXCLUDE_BELOW = 1e-3


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float], keep: List[str],
              floor: float) -> float:
    worst = 0.0
    for n in keep:
        p = prog.get(n, math.nan)
        gap = abs(p - ref[n]) / max(ref[n], floor)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    g_med = statistics.median(ref["grad"].values())
    keep = [n for n, v in ref["grad"].items() if v >= EXCLUDE_BELOW * g_med]
    if set(prog["grad"]) != set(ref["grad"]):
        return {"loss_gap": math.inf, "grad_gap": math.inf, "change_gap": math.inf}
    moved = [ref["change"][n] for n in keep if ref["change"][n] > 0]
    c_med = statistics.median(moved) if moved else math.inf
    loss = max((abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])),
               default=math.inf)
    if len(prog["loss"]) != len(ref["loss"]) or not math.isfinite(loss):
        loss = math.inf
    return {
        "loss_gap": loss,
        "grad_gap": _leaf_gap(prog["grad"], ref["grad"], keep, g_med),
        "change_gap": _leaf_gap(prog["change"], ref["change"], keep, c_med),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict]:
    """(correct, {name: {"value", "limit"}}), in the limits' order."""
    out = {n: {"value": values[n], "limit": limits[n]} for n in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"] for v in out.values())
    return ok, out
