"""The comparison that decides `correct`, and how its numbers are printed.

Training: the program's first dispatch against the reference that follows
the same rows. Three numbers, each with its limit from the configuration:
  loss_gap    |loss - reference's| / reference's at the FIRST step. The
              later steps' gaps ride along unjudged: minibatch AdaGrad
              moves an entry by +-eta whatever the size of its gradient,
              so an entry whose summed gradient cancels to rounding can
              step the other way on the two sides, and a later step's
              loss then swings by 1e-3 (FM) to 2e-2 (bf16 FFM) on a few
              seeds (PERF.md section 2 gives the readings)
  grad_gap    worst leaf of the gap between the norms of the accumulated
              gradient, sqrt(AdaGrad's sum of squares), as the optimizer
              got it over the dispatch's steps
  change_gap  worst leaf of the gap between the norms of the parameters'
              change over the dispatch
A gap of norms is measured against the reference's norm of that leaf or
of the median leaf, whichever is larger. A leaf whose reference gradient
is under a thousandth of the median leaf's moves by round-off alone and
is left out of change_gap."""

from __future__ import annotations

import sys
from typing import Dict

import numpy as np


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               skip=()) -> Dict[str, float]:
    med = float(np.median(list(ref.values())))
    return {leaf: abs(prog[leaf] - r) / max(r, med, 1e-30)
            for leaf, r in ref.items() if leaf not in skip}


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """`prog`/`ref`: {"losses": [...], "before"/"after"/"gg": {leaf: rows}}
    over the same table rows in the same order."""
    lp, lr = np.asarray(prog["losses"], np.float64), \
        np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape:
        return {"loss_gap": float("inf"), "grad_gap": float("inf"),
                "change_gap": float("inf")}
    leaves = list(ref["after"])
    grad_r = {n: _norm(np.sqrt(np.maximum(ref["gg"][n], 0))) for n in leaves}
    grad_p = {n: _norm(np.sqrt(np.maximum(prog["gg"][n], 0)))
              for n in leaves}
    chg_r = {n: _norm(ref["after"][n] - ref["before"][n]) for n in leaves}
    chg_p = {n: _norm(prog["after"][n] - prog["before"][n]) for n in leaves}
    med = float(np.median(list(grad_r.values())))
    still = [n for n in leaves if grad_r[n] < 1e-3 * med]
    step_gaps = np.abs(lp - lr) / np.abs(lr)
    grad_gaps = _leaf_gaps(grad_p, grad_r)
    chg_gaps = _leaf_gaps(chg_p, chg_r, skip=still)
    out = {"loss_gap": float(step_gaps[0]),
           "grad_gap": max(grad_gaps.values()),
           "change_gap": max(chg_gaps.values(), default=0.0)}
    out = {k: (v if np.isfinite(v) else float("inf"))
           for k, v in out.items()}
    out["_step_loss_gaps"] = [float(v) for v in step_gaps]
    out["_leaf_gaps"] = {"grad": grad_gaps, "change": chg_gaps}
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """{"correct": bool, "compared": {name: {"value", "limit"}}}; a number
    with no limit in the configuration fails."""
    compared = {}
    ok = bool(numbers)
    for name, value in numbers.items():
        if name.startswith("_"):             # detail, not a number compared
            continue
        limit = limits.get(name)
        compared[name] = {"value": value, "limit": limit}
        if limit is None or not (value <= limit):
            ok = False
    return {"correct": ok, "compared": compared}


def print_compared(compared: dict) -> None:
    """The run's last lines on standard error: each number beside its
    limit."""
    for name, c in compared.items():
        print(f"compared {name} value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
