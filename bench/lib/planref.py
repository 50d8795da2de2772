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

A planner reads ``xi`` from Monte Carlo draws of its own, Algorithm 3's
``theta``, so where two arms' gains per cost, or two candidates' ``xi``,
lie within that Monte Carlo's error of each other it may take either, and
its greedy then goes on along another path. ``sur_greedy_floor`` is the
least ``xi`` SurGreedy can end on at that resolution: its greedy branches
on every pick within ``Z`` standard errors of the best, and its last
choice on every candidate within them of the top. A planned set below the
floor is no SurGreedy result of a planner reading ``theta`` draws.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .reference import P_FLOOR

SAMPLES = 16384
DRAWS_SEED = 2501_04901  # the reference's own draws: the same in every run
RATIO_RTOL = 1e-9
SLACK = 1e-15            # affordability slack on the remaining budget


class Xi:
    """``xi`` of arm sets under one estimate, by ``SAMPLES`` fixed draws
    (common to every set scored, so differences carry little noise). The
    class beliefs of M sets on every draw are two matrix products over the
    draws' one-hot answers."""

    def __init__(self, p: np.ndarray, num_classes: int, rng: np.random.Generator,
                 samples: int = SAMPLES):
        K = int(num_classes)
        pc = np.clip(np.asarray(p, np.float64), P_FLOOR, 1.0 - P_FLOOR)
        L = pc.size
        u = rng.random((2, samples, L))
        wrong = 1 + np.minimum((u[1] * (K - 1)).astype(np.int64), K - 2)
        ans = np.where(u[0] < pc[None, :], 0, wrong)          # truth is class 0
        self.w = np.log(pc) + np.log(K - 1.0) - np.log1p(-pc)
        # (K, L, S): whether arm l names class k on draw s
        self.onehot_t = (ans.T[None, :, :] == np.arange(K)[:, None, None]).astype(np.float64)
        self.ans_t = ans.T.copy()                                  # (L, S)
        p_min = pc.min()
        self.empty = np.log(p_min) - np.log(2.0) - np.log1p(-p_min)
        self.p = pc
        self.K = K

    def __call__(self, masks: np.ndarray) -> np.ndarray:
        """(M, L) bool -> (M,) ``xi`` of each set."""
        return self.per_draw(masks).mean(axis=1)

    def per_draw(self, masks: np.ndarray) -> np.ndarray:
        """(M, L) bool -> (M, S): on each draw, the share of the classes
        at the top of each set's vote that is the true one."""
        m = np.atleast_2d(np.asarray(masks, np.float64))
        vote = np.matmul(m * self.w[None, :], self.onehot_t)      # (K, M, S)
        voted = np.matmul(m, self.onehot_t) > 0
        bel = np.where(voted, vote, self.empty)
        at_top = bel == bel.max(axis=0, keepdims=True)
        return at_top[0] / at_top.sum(axis=0)

    def grown(self, chosen: np.ndarray, cand: np.ndarray) -> np.ndarray:
        """(M + 1, S): ``per_draw`` of the set ``chosen`` (L,) bool and of
        it grown by each arm of ``cand`` (M,), each arm's vote added to
        the set's on the class it names."""
        m = np.asarray(chosen, np.float64)[None, :]
        vote = np.matmul(m * self.w[None, :], self.onehot_t)[:, 0]  # (K, S)
        voted = np.matmul(m, self.onehot_t)[:, 0] > 0
        bel = np.where(voted, vote, self.empty)
        named = self.ans_t[cand]                                    # (M, S)
        new = np.take_along_axis(vote, named, axis=0) + self.w[cand][:, None]
        cls = np.where(named[None] == np.arange(self.K)[:, None, None],
                       new[None], bel[:, None, :])                 # (K, M, S)
        at_top = np.concatenate([bel[:, None, :], cls], axis=1)
        at_top = at_top == at_top.max(axis=0, keepdims=True)
        return at_top[0] / at_top.sum(axis=0)


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


# ---------------------------------------------------------------------------
# SurGreedy as a planner runs it: xi read from theta draws of its own
# ---------------------------------------------------------------------------

Z = 5.0            # standard errors within which two Monte Carlo readings tie
MAX_VISITS = 4096  # distinct arm sets one greedy may branch through
# Algorithm 3's Monte Carlo parameters, the paper's (Sec. 5), with which
# every deployment of this benchmark plans
EPS, DELTA = 0.1, 0.01


def theta(p: np.ndarray, costs: np.ndarray, budget: float) -> int:
    """Algorithm 3's Monte Carlo size for one (estimate, budget): ``(8 + 2
    eps) / (eps^2 p*) ln(2 L^2 / delta)`` draws at ``EPS`` and ``DELTA``,
    ``p*`` the largest clipped ``p`` among the affordable arms (1 where none
    is)."""
    eps, delta = EPS, DELTA
    pc = np.clip(np.asarray(p, np.float64), P_FLOOR, 1.0 - P_FLOOR)
    afford = costs <= budget + SLACK
    p_star = float(pc[afford].max()) if afford.any() else 1.0
    L = pc.size
    return int(np.ceil((8.0 + 2.0 * eps) / (eps * eps * p_star)
                       * np.log(2.0 * L * L / delta)))


def _ties(x: np.ndarray, theta_n: int) -> np.ndarray:
    """(M,) bool: the rows of ``x`` (M, S), per-draw readings, whose mean a
    reader of ``theta_n`` draws of its own could put at the top: within
    ``Z`` standard errors of the largest mean, the error that of the
    per-draw difference over the reader's draws and these together."""
    mean = x.mean(axis=1)
    best = int(np.argmax(mean))
    sd = (x[best][None, :] - x).std(axis=1)
    width = Z * sd * np.sqrt(1.0 / theta_n + 1.0 / x.shape[1])
    return mean[best] - mean <= width + SLACK + RATIO_RTOL * abs(mean[best])


def _ends(xi: Xi, costs: np.ndarray, budget: float, theta_n: Optional[int],
          toward: Optional[np.ndarray] = None):
    """Yield the ends of ``greedy_ends`` as the search meets them, the
    branches into the set ``toward`` searched first."""
    L = costs.size
    first = np.zeros(L, bool) if toward is None else np.asarray(toward, bool)
    seen = set()
    stack = [np.zeros(L, bool)]
    while stack:
        chosen = stack.pop()
        key = chosen.tobytes()
        if key in seen:
            continue
        seen.add(key)
        if len(seen) > MAX_VISITS:
            raise RuntimeError(f"greedy on xi branches past {MAX_VISITS} sets")
        left = budget - float(costs[chosen].sum()) + SLACK
        afford = ~chosen & (costs <= left)
        if not afford.any():
            yield chosen
            continue
        if costs[~chosen].sum() <= left:
            yield np.ones(L, bool)
            continue
        cand = np.flatnonzero(afford)
        x = xi.grown(chosen, cand)
        ratios = (x[1:] - x[0][None, :]) / costs[cand][:, None]
        if theta_n is None:
            r = ratios.mean(axis=1)
            tied = np.abs(r - r.max()) <= SLACK + RATIO_RTOL * abs(r.max())
            picks = cand[tied][[np.argmax((xi.p / costs)[cand[tied]])]]
        else:
            picks = cand[_ties(ratios, theta_n)]
        for j in sorted(picks.tolist(), key=lambda a: bool(first[a])):
            nxt = chosen.copy()
            nxt[j] = True
            stack.append(nxt)


def greedy_ends(xi: Xi, costs: np.ndarray, budget: float,
                theta_n: Optional[int] = None) -> np.ndarray:
    """(E, L) bool: the sets Algorithm 1 on ``xi`` ends on. With
    ``theta_n`` None, the one set of ``_greedy``; else every set it can end
    on when it reads ``xi`` from ``theta_n`` draws: each round branches on
    every affordable arm whose gain per cost ties with the best
    (``_ties``). Paths that reach the same set share their future; where
    every arm left fits the budget left, every path ends on all of them."""
    return np.unique(np.stack(list(_ends(xi, costs, budget, theta_n))), axis=0)


def _fixed(xi: Xi, costs: np.ndarray, budget: float) -> np.ndarray:
    """(2, S) per-draw readings of Algorithm 2's candidates that draw no
    sample: the affordable arm of largest ``p``, the greedy on ``gamma``."""
    log_miss = np.log1p(-xi.p)
    gamma = lambda m: 1.0 - np.exp(np.asarray(m, np.float64) @ log_miss)  # noqa: E731
    return xi.per_draw(np.stack([best_single(xi, costs, budget),
                                 _greedy(xi.p, costs, budget, gamma)]))


def sur_greedy_floor(xi: Xi, costs: np.ndarray, budget: float, theta_n: int,
                     enough: float = -np.inf,
                     toward: Optional[np.ndarray] = None) -> float:
    """The least ``xi`` SurGreedy can return when it reads ``xi`` from
    ``theta_n`` draws: over every end of its greedy on ``xi``
    (``greedy_ends``), the candidates that tie at the top (``_ties``) of
    {the affordable arm of largest ``p``, that end, the greedy on
    ``gamma``}, and the least of their ``xi``. It is at most the ``xi``
    of ``sur_greedy``: SurGreedy's result on these draws is one of them.

    The search stops at the first value at or below ``enough``, which it
    returns (the floor is then no higher); it follows the arms of
    ``toward`` first."""
    if not (costs <= budget + SLACK).any():
        return float(xi(np.zeros((1, costs.size), bool))[0])
    fixed = _fixed(xi, costs, budget)
    floor = np.inf
    seen = set()
    for end in _ends(xi, costs, budget, theta_n, toward):
        if end.tobytes() in seen:
            continue
        seen.add(end.tobytes())
        x = np.vstack([fixed[:1], xi.per_draw(end[None, :]), fixed[1:]])
        floor = min(floor, float(x.mean(axis=1)[_ties(x, theta_n)].min()))
        if floor <= enough:
            break
    return floor
