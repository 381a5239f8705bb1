"""The comparison that decides ``correct`` for a training cell.

Both sides report each step's loss, each leaf's first gradient norm (the
gradient as the optimizer gets it) and each leaf's change after the
checked steps. Four numbers are compared:

* ``loss_gap``: ``|L_prog - L_ref| / |L_ref|`` of the first step's loss.
  The later steps' losses follow Adam's first, sign-like updates, in which
  a gradient near zero flips with rounding, and they swing from seed to
  seed; they are printed, not compared;
* ``grad_gap``: the worst leaf's ``|n_prog - n_ref| / max(n_ref, median
  leaf n_ref)`` for the norm of the first gradient;
* ``change_gap``: the median leaf's gap, in the same measure, for the norm
  of each leaf's change after the checked steps. The worst leaf is a
  layer-norm gain: at 1.0 a bf16 weight moves in steps of 2^-8 down and
  2^-7 up, and an Adam step of lr = 4e-3 sits on the rounding edge of the
  upward one, so its change swings by 10-20% between any two runs. Leaves
  whose reference gradient is under ``NOUGHT`` of the median leaf's move
  under Adam by round-off alone, and are left out;
* ``table_change_gap``: the item table's gap, in the same measure, for
  the norm of its change after the checked steps: the rows that the sparse
  update landed (one leaf among many, so the median never sees it).

Each number has a limit of its own, from the cell's limits file.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Tuple

NOUGHT = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap", "table_change_gap")


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep=None) -> Dict[str, float]:
    """Each leaf's ``|n_prog - n_ref| / max(n_ref, median leaf n_ref)``."""
    if set(prog) != set(ref):
        return {"leaves differ": math.inf}
    floor = statistics.median(ref.values())
    out = {}
    for n in ref:
        if keep is not None and n not in keep:
            continue
        p, r = prog[n], ref[n]
        ok = math.isfinite(p) and math.isfinite(r)
        out[n] = abs(p - r) / max(r, floor, 1e-30) if ok else math.inf
    return out


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    a, b = prog["losses"][0], ref["losses"][0]
    loss_gap = abs(a - b) / abs(b) if math.isfinite(a) else math.inf
    g = ref["grad_norms"]
    med = statistics.median(g.values())
    moved = {n for n, v in g.items() if v >= NOUGHT * med}
    change = leaf_gaps(prog["change_norms"], ref["change_norms"], moved)
    return {"loss_gap": loss_gap,
            "grad_gap": max(leaf_gaps(prog["grad_norms"], g).values()),
            "change_gap": statistics.median(change.values()),
            "table_change_gap": change.get("table", math.inf)}


def judge(found: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    ok = all(math.isfinite(found[n]) and found[n] <= limits[n]
             for n in NUMBERS)
    # a number that is not finite fails; it is printed as a huge one so
    # that the result stays plain JSON
    checks = {n: {"value": found[n] if math.isfinite(found[n]) else 1e300,
                  "limit": limits[n]} for n in NUMBERS}
    return ok, checks
