"""The comparison that decides ``correct`` for a training cell.

The program's readings (``harness.Capture``: its own first three steps,
taken from the timed path during set-up) are compared with the float32
reference's (``reference/train.py``), computed after the window from the
same seed. The numbers compared, each with the limit the cell's file
``limits/<workload>.json`` gives:

* ``loss``: the largest relative gap between the two losses of a step,
  over the three steps;
* ``grad_norm``: over the parameter leaves, the largest gap between the
  two norms of the first gradient as AdamW received it, relative to the
  reference's norm of that leaf or of the median leaf, whichever is
  larger;
* ``param_change``: the same for each leaf's change over the three
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by AdamW's rounding alone);
* ``selection``: over the steps, the largest shortfall of the program's
  decision: on a refresh step by the reference's own scores
  (``reference/select.py:shortfall``), a pivot's relative shortfall
  against the best row still available, or the rank rule broken, in
  projection error; on any other step 1 if the decision is not the last
  refresh's, kept. The reference then trains on the rows the program
  chose, so the three numbers above compare the training arithmetic on
  the same rows.

A run is correct when every number is at or under its limit.
"""
from __future__ import annotations

import json
import os
import statistics
from typing import Any, Dict, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def _norm_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: Optional[set] = None) -> Tuple[float, str]:
    """(largest relative gap, the leaf that has it)."""
    names = [n for n in ref if keep is None or n in keep]
    med = statistics.median(ref[n] for n in names)
    return max((abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30), n)
               for n in names)


def numbers(prog: Dict[str, Any], ref: Dict[str, Any],
            where: Optional[Dict[str, str]] = None) -> Dict[str, float]:
    """The compared numbers; ``where`` gets the leaf behind each norm gap."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                  ref["losses"]))
    grad, grad_leaf = _norm_gap(prog["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    moving = {n for n, v in ref["grad_norms"].items() if v >= 1e-3 * med}
    change, change_leaf = _norm_gap(prog["change_norms"],
                                    ref["change_norms"], moving)
    if where is not None:
        where.update(grad_norm=grad_leaf, param_change=change_leaf)
    return {"loss": loss, "grad_norm": grad, "param_change": change,
            "selection": max(ref["shortfalls"])}


def limits(workload: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "limits", workload + ".json")) as f:
        return json.load(f)["limits"]


def check(cell, seed: int, prog: Dict[str, Any]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Run the reference for ``cell`` and compare; returns (correct,
    {number: {"value", "limit"}})."""
    import time

    from chipbench.reference import train as ref_train
    t = time.time()
    ref = ref_train.steps(cell.config, cell.traffic, cell.batch, seed,
                          n_steps=len(prog["losses"]), follow=prog["select"])
    where: Dict[str, str] = {}
    got = numbers(prog, ref, where)
    lim = limits(cell.name)
    compared = {k: {"value": v, "limit": lim[k]} for k, v in got.items()}
    ok = all(v <= lim[k] for k, v in got.items())
    from chipbench.harness import log
    own = [(s["pivots"], s["rank"]) for s in ref["decided"]]
    log(f"reference: {time.time() - t:.2f} s; losses {ref['losses']} own "
        f"decisions {own} margins {ref['margins']}; worst leaves {where}")
    return ok, compared
