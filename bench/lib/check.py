"""The comparison that decides ``correct``.

Every request due in the measured window is compared with the plain
references: ``reference.route`` (the data plane) under the benchmark's own
calibration (``reference.Calibration``) and the arm set the program planned
for the request, and ``planref`` (the planner) on that set. Five numbers,
each against its limit from the configuration's ``correct_limits``:

* ``missing``: requests due in the window that never completed;
* ``mismatched``: requests whose prediction or stop wave differs from the
  reference's;
* ``cost_gap``: the widest gap between a request's cost and the
  reference's, as a share of the reference's cost (floored at the cheapest
  arm's price);
* ``over_budget``: requests whose planned arm set costs more than the
  request's budget (ThriftLLM's budget constraint);
* ``plan_xi_gap``: the widest shortfall of a planned set's correctness
  probability below the reference SurGreedy's set for the same cluster and
  budget (``planref.plan_gap``).

A deployment with online feedback (its configuration has ``feedback``)
is compared under the reference's own replay of the loop (``fold``): each
request against the snapshot of its cluster's estimate in force when its
group was dispatched, and ``plan_xi_gap`` per snapshot against the least
SurGreedy can return at the planner's Monte Carlo resolution
(``fold.plan_gap``); and has a sixth number, where its limits name it:

* ``gate_disagreements``: clusters, summed over the program's folds, whose
  drift gate the program and the reference decided differently.

``readings`` computes them for any candidate outputs, so the control is
read by the same code.
"""
from __future__ import annotations

import numpy as np

CHECKS = ("missing", "mismatched", "cost_gap", "over_budget", "plan_xi_gap")
FEEDBACK_CHECKS = ("gate_disagreements",)


def names(limits: dict) -> tuple:
    """The numbers a configuration's limits hold: the five, and the
    feedback loop's where its limits name them."""
    return CHECKS + tuple(k for k in FEEDBACK_CHECKS if k in limits)


def readings(done, predictions, stop_waves, costs, ref, planned_cost,
             budgets, price_floor, plan_xi_gap) -> dict:
    """The five numbers of one comparison.

    ``done`` (N,) bool marks requests that completed; the other arrays are
    (N,) and aligned with it; ``ref`` is ``(predictions, stop_waves,
    costs)`` of the reference."""
    r_pred, r_stop, r_cost = ref
    d = np.asarray(done, bool)
    differ = d & ((predictions != r_pred) | (stop_waves != r_stop))
    gap = np.abs(costs - r_cost) / np.maximum(np.abs(r_cost), price_floor)
    return {
        "missing": int((~d).sum()),
        "mismatched": int(differ.sum()),
        "cost_gap": float(gap[d].max()) if d.any() else 0.0,
        "over_budget": int((planned_cost > budgets * (1.0 + 1e-9)).sum()),
        "plan_xi_gap": float(plan_xi_gap),
    }


def verdict(values: dict, limits: dict) -> bool:
    """True when every number is within its limit."""
    return all(values[k] <= limits[k] for k in names(limits))


def lines(values: dict, limits: dict) -> list:
    """One line per number, with its limit, for standard error."""
    return [f"check {k}: {values[k]!r} (limit {limits[k]!r})" for k in names(limits)]


def as_json(values: dict, limits: dict) -> dict:
    return {k: {"value": values[k], "limit": limits[k]} for k in names(limits)}
