"""The plain reference of the online feedback loop: a streaming fold of the
returned labels and a two-sided Wilson drift gate, written from their
definitions, not from the program.

Per query cluster of the calibration the reference keeps an estimate
``p`` (L,) with per-arm counts ``n`` (L,), started from the benchmark's own
history (every arm observed once per history query of the cluster), and a
plan-visible snapshot ``(p_s, n_s)``, the estimate every request of the
cluster is planned and routed under.

* A label for a served request scores each arm the request asked: the
  first ``stop`` arms of its planned set in the reference's wave order
  (decreasing clipped ``p`` of the snapshot it was routed under, ties by
  arm index), plus the arm an exploration probe asked, if any. Scores are
  buffered per cluster: successes and attempts per arm.
* At each admission boundary the program folded at, the buffered counts
  fold in: ``n' = n + a`` and ``p' = (p n + s) / n'`` on arms with ``a >
  0``; the others keep their estimate.
* The gate: the two-sided Wilson score interval at level ``1 - delta`` of
  ``(p', n')`` and of the snapshot, per arm; where they are disjoint on any
  arm with ``a > 0`` the cluster has drifted, and its snapshot moves to
  ``(p', n')``. Requests dispatched after the boundary are routed under it.

``replay`` runs the loop over what a run recorded (the boundaries in
order, the labels returned before each, the probes) and counts the
boundaries at which the program's gate and the reference's disagree.

``plan_gap`` scores each planned set against the least SurGreedy can
return on the snapshot it was planned under, at the planner's own Monte
Carlo resolution (``planref.sur_greedy_floor``).
"""
from __future__ import annotations

import concurrent.futures
import statistics
from typing import List, Sequence, Tuple

import numpy as np

from . import planref, reference

PLAN_THREADS = 4   # threads that score planned sets after the window


def wilson(p: np.ndarray, n: np.ndarray, delta: float):
    """Two-sided Wilson score interval ``(lo, hi)`` at level ``1 - delta``
    of a share ``p`` seen over ``n`` trials; ``[0, 1]`` where ``n == 0``."""
    dt = np.asarray(p).dtype
    z = dt.type(statistics.NormalDist().inv_cdf(1.0 - delta / 2.0))
    n_ = np.maximum(n, dt.type(1.0))
    z2n = z * z / n_
    centre = (p + z2n / 2) / (1 + z2n)
    half = z / (1 + z2n) * np.sqrt(p * (1 - p) / n_ + z2n / (4 * n_))
    lo = np.clip(centre - half, 0, 1)
    hi = np.clip(centre + half, 0, 1)
    return np.where(n > 0, lo, 0), np.where(n > 0, hi, 1)


def fold(p: np.ndarray, n: np.ndarray, succ: np.ndarray, att: np.ndarray):
    """The streaming mean: ``(p', n')`` after ``succ`` successes in ``att``
    attempts per arm."""
    n2 = n + att
    return np.where(att > 0, (p * n + succ) / np.maximum(n2, 1), p), n2


def drifted(p_s, n_s, p, n, observed, delta) -> np.ndarray:
    """(C,) bool: a disjoint pair of intervals on an observed arm."""
    lo_s, hi_s = wilson(p_s, n_s, delta)
    lo, hi = wilson(p, n, delta)
    return (((lo > hi_s) | (hi < lo_s)) & observed).any(axis=-1)


def wave_order(p: np.ndarray, arm_set: np.ndarray) -> np.ndarray:
    """(N, L) arm ids in each request's wave order: its planned arms by
    decreasing clipped ``p`` (ties by arm index), unplanned last."""
    pc = np.clip(p, reference.P_FLOOR, 1.0 - reference.P_FLOOR)
    return np.argsort(np.where(arm_set, -pc, np.inf), axis=1, kind="stable")


class Replay:
    """The reference's run of the loop over a recorded run.

    ``rows`` (R,) are the traffic rows the program routed, in dispatch
    order; ``group`` (R,) the index of the routed group each belonged to,
    ``cluster`` (R,) its calibration row, ``arm_set`` (R, L) the arms
    planned for it. ``answers`` (L, N) and ``labels`` (N,) are the
    traffic's."""

    def __init__(self, p0, n0, delta, rows, group, cluster, arm_set, answers,
                 labels, costs, num_classes, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        self.p = np.asarray(p0, self.dtype).copy()
        self.n = np.asarray(n0, self.dtype).copy()
        self.delta = float(delta)
        self.snaps_p: List[np.ndarray] = list(self.p.copy())
        self.snaps_n: List[np.ndarray] = list(self.n.copy())
        self.snap_of_cluster = np.arange(self.p.shape[0])
        self.rows = np.asarray(rows, np.int64)
        self.group = np.asarray(group, np.int64)
        self.cluster = np.asarray(cluster, np.int64)
        self.arm_set = np.asarray(arm_set, bool)
        self.answers = answers
        self.labels = labels
        self.costs = costs
        self.K = int(num_classes)
        R = self.rows.size
        self.pos_of_row = np.full(answers.shape[1], -1, np.int64)
        self.pos_of_row[self.rows] = np.arange(R)
        self.snap = self.cluster.copy()
        self.pred = np.zeros(R, np.int64)
        self.stop = np.zeros(R, np.int64)
        self.cost = np.zeros(R, np.float64)
        self.asked = np.zeros((R, self.arm_set.shape[1]), bool)
        self.fires = 0
        self.fired_log: List[set] = []      # calibration rows fired, per boundary
        self._route(np.arange(R))

    def _route(self, pos: np.ndarray) -> None:
        if pos.size == 0:
            return
        p = np.stack(self.snaps_p)[self.snap[pos]]
        sets = self.arm_set[pos]
        ans = self.answers[:, self.rows[pos]].T
        pred, stop, cost = reference.route(p, sets, ans, self.costs, self.K,
                                           dtype=self.dtype)
        self.pred[pos], self.stop[pos], self.cost[pos] = pred, stop, cost
        order = wave_order(p, sets)
        first = np.arange(sets.shape[1])[None, :] < stop[:, None]
        asked = np.zeros_like(sets)
        np.put_along_axis(asked, order, first, axis=1)
        self.asked[pos] = asked

    def boundary(self, group_index: int, label_rows: np.ndarray,
                 probe_arm: np.ndarray) -> set:
        """Fold the labels of ``label_rows`` (traffic rows) at the boundary
        before routed group ``group_index``; ``probe_arm`` (N,) is the arm
        each request's probe asked, -1 for none. Returns the calibration
        rows whose gate fired."""
        pos = self.pos_of_row[label_rows]
        if np.any(pos < 0):
            raise ValueError("a label came back for a request never routed")
        C, L = self.p.shape
        succ = np.zeros((C, L), self.dtype)
        att = np.zeros((C, L), self.dtype)
        asked = self.asked[pos].copy()
        probe = probe_arm[label_rows]
        has = probe >= 0
        asked[np.flatnonzero(has), probe[has]] = True
        hit = self.answers[:, label_rows].T == self.labels[label_rows][:, None]
        cl = self.cluster[pos]
        np.add.at(succ, cl, (asked & hit).astype(self.dtype))
        np.add.at(att, cl, asked.astype(self.dtype))
        touched = np.unique(cl)
        p2, n2 = fold(self.p[touched], self.n[touched], succ[touched], att[touched])
        snap = self.snap_of_cluster[touched]
        fire = drifted(np.stack(self.snaps_p)[snap], np.stack(self.snaps_n)[snap],
                       p2, n2, att[touched] > 0, self.delta)
        self.p[touched], self.n[touched] = p2, n2
        fired = touched[fire]
        for c in fired:
            self.snap_of_cluster[c] = len(self.snaps_p)
            self.snaps_p.append(self.p[c].copy())
            self.snaps_n.append(self.n[c].copy())
        if fired.size:
            self.fires += int(fired.size)
            later = np.flatnonzero((self.group >= group_index)
                                   & np.isin(self.cluster, fired))
            self.snap[later] = self.snap_of_cluster[self.cluster[later]]
            self._route(later)
        return set(fired.tolist())


def replay(p0, n0, delta, rows, group, cluster, arm_set, answers, labels,
           costs, num_classes, boundaries: Sequence[Tuple[int, set]],
           label_events: Sequence[Tuple[int, np.ndarray]],
           probe_arm: np.ndarray, dtype=np.float64):
    """Run the loop over a recorded run. ``boundaries`` holds, per fold
    the program made, ``(routed groups before it, calibration rows whose
    gate the program fired)``; ``label_events`` ``(folds before it,
    traffic rows)`` per batch of labels returned. Returns ``(Replay,
    gate_disagreements)``."""
    rp = Replay(p0, n0, delta, rows, group, cluster, arm_set, answers, labels,
                costs, num_classes, dtype=dtype)
    by_fold: dict = {}
    for f, lrows in label_events:
        by_fold.setdefault(int(f), []).append(np.asarray(lrows, np.int64))
    disagree = 0
    for f, (group_index, program_fired) in enumerate(boundaries):
        lrows = by_fold.get(f)
        if not lrows:
            fired: set = set()
        else:
            fired = rp.boundary(int(group_index), np.concatenate(lrows), probe_arm)
        rp.fired_log.append(fired)
        disagree += len(fired ^ set(program_fired))
    return rp, disagree


def plan_gap(snaps_p: Sequence[np.ndarray], costs: np.ndarray, num_classes: int,
             snap: np.ndarray, budgets: np.ndarray, sets: np.ndarray,
             draws_seed: int = planref.DRAWS_SEED) -> float:
    """The widest shortfall of a planned set's ``xi`` below the least that
    SurGreedy can return for the same snapshot and budget when it reads
    ``xi`` from Algorithm 3's ``planref.theta`` draws
    (``planref.sur_greedy_floor``), every set scored on the same fixed
    draws of that snapshot (``planref.Xi``, drawn from ``draws_seed`` and
    the snapshot's index). 0 where every planned set reaches it.

    That floor lies at most at SurGreedy's own result on the draws, so a
    set that reaches the result needs no more, and the floor is sought
    only where the result less the set could still widen the gap, the
    widest first, along the planned set's own arms first, and only until
    it is found low enough not to widen it."""
    xis = lambda s: planref.Xi(snaps_p[s], num_classes,  # noqa: E731
                               np.random.default_rng([draws_seed, s]))

    def bounds(s: int) -> list:
        """(bound on the shortfall, snapshot, budget, xi of the worst set)."""
        xi = xis(s)
        rows = np.flatnonzero(snap == s)
        out = []
        for b in np.unique(budgets[rows]).tolist():
            planned = np.unique(sets[rows[budgets[rows] == b]], axis=0)
            x = xi(planned)
            got, worst = float(x.min()), planned[int(np.argmin(x))]
            own = planref.sur_greedy(xi, costs, b)
            out.append((float(xi(own[None, :])[0]) - got, s, b, got, worst))
        return out

    # numpy leaves the interpreter's lock in its array work: snapshots run
    # side by side on a few threads, once the window has closed
    with concurrent.futures.ThreadPoolExecutor(PLAN_THREADS) as pool:
        short = [t for ts in pool.map(bounds, np.unique(snap).tolist()) for t in ts
                 if t[0] > 0.0]
    gap = 0.0
    for bound, s, b, got, worst in sorted(short, key=lambda t: -t[0]):
        if bound <= gap:
            break
        n = planref.theta(snaps_p[s], costs, b)
        floor = planref.sur_greedy_floor(xis(s), costs, b, n, enough=got + gap,
                                         toward=worst)
        gap = max(gap, floor - got)
    return gap
