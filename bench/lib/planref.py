"""The plain reference planner: SurGreedyLLM (the paper's Algorithm 2) with
a Monte Carlo of its own, and the correctness probability of any arm set.

Written from the paper, not from the program. Under an estimate ``p`` (L,)
of a query cluster, ``xi(S)`` is the probability that the
maximum-likelihood vote of the arms of ``S`` names the true class when arm
l answers right with probability ``p_l`` and otherwise names one of the
K-1 wrong classes uniformly (Eq. 1). The vote is the one ``reference.route``
takes (Eq. 4 log weights, the no-vote belief); a tie at the top counts as
the share of the tied classes that is true, so ``xi`` of the empty set is
``1/K``.

SurGreedy returns the best, by ``xi``, of three candidates under the
budget: the affordable arm of largest ``p``; greedy on ``xi`` (each round
adds the affordable arm of largest gain per cost, ties by ``p`` per cost);
greedy on the surrogate ``gamma(S) = 1 - prod(1 - p_l)`` the same way.

``plan_gap`` scores the program's planned set against the reference's
choice for the same cluster and budget, both by the same draws. The draws
are the reference's own and fixed (``DRAWS_SEED``): the program plans
deterministically from its calibration, so the reading is the same in
every run and moves only where the program's choice does.
"""
from __future__ import annotations

import numpy as np

from .reference import P_FLOOR

SAMPLES = 16384
DRAWS_SEED = 2501_04901  # the reference's own draws: the same in every run
RATIO_RTOL = 1e-9
SLACK = 1e-15            # affordability slack on the remaining budget


class Xi:
    """``xi`` of arm sets under one estimate, by ``SAMPLES`` fixed draws
    (common to every set scored, so differences carry little noise)."""

    def __init__(self, p: np.ndarray, num_classes: int, rng: np.random.Generator,
                 samples: int = SAMPLES):
        K = int(num_classes)
        pc = np.clip(np.asarray(p, np.float64), P_FLOOR, 1.0 - P_FLOOR)
        L = pc.size
        u = rng.random((2, samples, L))
        wrong = 1 + np.minimum((u[1] * (K - 1)).astype(np.int64), K - 2)
        ans = np.where(u[0] < pc[None, :], 0, wrong)          # truth is class 0
        w = np.log(pc) + np.log(K - 1.0) - np.log1p(-pc)
        self.onehot = (ans[:, :, None] == np.arange(K)).astype(np.float64)  # (S, L, K)
        self.vote = self.onehot * w[None, :, None]
        p_min = pc.min()
        self.empty = np.log(p_min) - np.log(2.0) - np.log1p(-p_min)
        self.p = pc
        self.K = K

    def __call__(self, masks: np.ndarray) -> np.ndarray:
        """(M, L) bool -> (M,) ``xi`` of each set."""
        m = np.atleast_2d(np.asarray(masks, np.float64))
        vote = np.einsum("slk,ml->msk", self.vote, m)
        voted = np.einsum("slk,ml->msk", self.onehot, m) > 0
        bel = np.where(voted, vote, self.empty)
        top = bel.max(axis=2, keepdims=True)
        at_top = bel == top
        return (at_top[:, :, 0] / at_top.sum(axis=2)).mean(axis=1)


def xis_for(p_by_cluster: np.ndarray, num_classes: int) -> list:
    """One ``Xi`` per cluster row of ``p_by_cluster`` (C, L), from the
    reference's fixed draws, so a planned set reads the same in every run."""
    rng = np.random.default_rng(DRAWS_SEED)
    return [Xi(p, num_classes, rng) for p in p_by_cluster]


def _greedy(p, costs, budget, value) -> np.ndarray:
    """Algorithm 1 on a set function ``value((M, L) masks) -> (M,)``."""
    L = p.size
    chosen = np.zeros(L, bool)
    spent = 0.0
    current = float(value(chosen[None, :])[0])
    while True:
        afford = ~chosen & (costs <= budget - spent + SLACK)
        if not afford.any():
            return chosen
        cand = np.flatnonzero(afford)
        masks = np.repeat(chosen[None, :], cand.size, axis=0)
        masks[np.arange(cand.size), cand] = True
        vals = value(masks)
        ratios = (vals - current) / costs[cand]
        best = ratios.max()
        tied = np.abs(ratios - best) <= SLACK + RATIO_RTOL * abs(best)
        pick = int(cand[tied][np.argmax((p / costs)[cand[tied]])])
        chosen[pick] = True
        spent += float(costs[pick])
        current = float(vals[np.flatnonzero(cand == pick)[0]])


def sur_greedy(xi: Xi, costs: np.ndarray, budget: float) -> np.ndarray:
    """The reference's planned set (L,) bool for one cluster and budget."""
    p, L = xi.p, xi.p.size
    afford = costs <= budget + SLACK
    if not afford.any():
        return np.zeros(L, bool)
    single = np.zeros(L, bool)
    single[np.flatnonzero(afford)[np.argmax(p[afford])]] = True
    log_miss = np.log1p(-p)
    gamma = lambda m: 1.0 - np.exp(np.asarray(m, np.float64) @ log_miss)  # noqa: E731
    cands = np.stack([single, _greedy(p, costs, budget, xi),
                      _greedy(p, costs, budget, gamma)])
    return cands[int(np.argmax(xi(cands)))]


def best_single(xi: Xi, costs: np.ndarray, budget: float) -> np.ndarray:
    """The affordable arm of largest ``p`` alone: Algorithm 2's first
    candidate without its greedy ones (the planner's control)."""
    afford = costs <= budget + SLACK
    out = np.zeros(xi.p.size, bool)
    if afford.any():
        out[np.flatnonzero(afford)[np.argmax(xi.p[afford])]] = True
    return out


def plan_gap(xis: list, costs: np.ndarray, pairs: np.ndarray,
             sets: np.ndarray) -> float:
    """The widest shortfall ``xi(reference set) - xi(planned set)`` over the
    distinct (cluster, budget, planned set) triples served.

    ``xis`` holds one ``Xi`` per cluster row of the calibration; ``pairs``
    (M, 2) the (cluster row, budget) of each request and ``sets`` (M, L)
    bool the set planned for it."""
    gap = 0.0
    for c in np.unique(pairs[:, 0]):
        xi = xis[int(c)]
        rows = np.flatnonzero(pairs[:, 0] == c)
        for b in np.unique(pairs[rows, 1]):
            ref = sur_greedy(xi, costs, float(b))
            got = np.unique(sets[rows[pairs[rows, 1] == b]], axis=0)
            vals = xi(np.vstack([ref[None, :], got]))
            gap = max(gap, float(vals[0] - vals[1:].min()))
    return gap
