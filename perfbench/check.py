"""The comparison that decides ``correct``: the program's first rounds
against the plain reference's.

Numbers compared (each against its limit in ``limits/<workload>.json``):

* ``loss`` — the evaluation after each of the first rounds (the held-out
  loss): the largest |program − reference| / |reference|;
* ``grad`` — √(Σ‖G‖²) per worker after round 1, every oracle gradient of
  that round as the optimizer got it (AdaSEG keeps only this sum, not a
  per-leaf gradient): the largest relative gap over workers;
* ``change`` — per leaf, the norm of the anchors' change from round 1 to
  the last round compared, all workers stacked: the largest gap between
  the program's norm and the reference's, over the reference's norm of
  that leaf or of the median leaf, whichever is larger. Leaves whose
  first gradient in the reference is under a thousandth of the median
  leaf's (a key bias under softmax) move by round-off alone and are left
  out by that rule, not by name.
"""
from __future__ import annotations

import math
import statistics

STILL = 1e-3        # a leaf's first gradient below this × the median's


def counted_leaves(first_grad: list[float]) -> list[int]:
    med = statistics.median(first_grad)
    return [i for i, g in enumerate(first_grad) if g >= STILL * med]


def _rel(p: float, r: float) -> float:
    if not (math.isfinite(p) and math.isfinite(r)):
        return math.inf
    return abs(p - r) / max(abs(r), 1e-30)


def gaps(prog: dict, ref: dict) -> dict:
    loss = max(_rel(p, r) for p, r in zip(prog["loss"], ref["loss"]))
    grad = max(_rel(math.sqrt(max(p, 0.0)), math.sqrt(max(r, 0.0)))
               for p, r in zip(prog["grad_sq"], ref["grad_sq"]))
    keep = counted_leaves(ref["first_grad"])
    med = statistics.median(ref["change"][i] for i in keep)
    change = 0.0
    for i in keep:
        p, r = prog["change"][i], ref["change"][i]
        gap = (abs(p - r) / max(r, med, 1e-30)
               if math.isfinite(p) else math.inf)
        change = max(change, gap)
    return {"loss": loss, "grad": grad, "change": change}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when every one is within."""
    table = {k: {"value": numbers[k], "limit": limits[k]["limit"]}
             for k in sorted(limits)}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
