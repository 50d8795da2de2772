"""The plain reference router, one request at a time in meaning.

Written from the paper, not from the program: ThriftLLM's adaptive
invocation (Algorithm 3) with the maximum-likelihood aggregation of Eq. 4
and the early stop of Prop. 4.

For a request whose cluster estimate is ``p`` (L,) and whose planned arm
set is ``S``:

* probabilities are clipped to ``[P_FLOOR, 1 - P_FLOOR]``;
* arm l's log weight is ``log p_l + log(K - 1) - log(1 - p_l)``;
* a class with no vote has the log belief ``log p_min - log 2 - log(1 -
  p_min)``, ``p_min`` over all L arms of the estimate;
* the arms of ``S`` are asked in decreasing ``p`` (ties keep arm order);
* before each wave, with ``h1 >= h2`` the two largest class beliefs and
  ``F`` the summed log weights of the arms not yet asked, the request stops
  unless ``F + h2 > h1 - STOP_MARGIN``;
* the prediction is the first class of largest belief, the cost the sum of
  the prices of the arms asked, the stop wave the number of arms asked.

Rows are computed side by side with numpy, wave by wave; no row reads
another's. ``dtype`` sets the precision of every float: float64 is what the
configuration states, float32 is the control.
"""
from __future__ import annotations

import numpy as np

P_FLOOR = 1e-4
STOP_MARGIN = 1e-9


def route(p: np.ndarray, arm_set: np.ndarray, answers: np.ndarray,
          costs: np.ndarray, num_classes: int, dtype=np.float64):
    """Reference outputs for N requests.

    Args:
      p: (N, L) the cluster estimate each request was planned under.
      arm_set: (N, L) bool, the arms planned for each request.
      answers: (N, L) each arm's answer to each request.
      costs: (L,) USD per call.

    Returns ``(predictions (N,), stop_waves (N,), costs (N,))``.
    """
    f = np.dtype(dtype).type
    N, L = p.shape
    K = int(num_classes)
    pc = np.clip(p.astype(dtype), f(P_FLOOR), f(1.0 - P_FLOOR))
    w = np.log(pc) + np.log(f(K - 1)) - np.log1p(-pc)
    p_min = pc.min(axis=1)
    empty = np.log(p_min) - np.log(f(2.0)) - np.log1p(-p_min)
    # wave order: planned arms by decreasing p (stable), unplanned last
    key = np.where(arm_set, -pc, np.inf)
    order = np.argsort(key, axis=1, kind="stable")
    n_arms = arm_set.sum(axis=1)
    rows = np.arange(N)
    w_ord = w[rows[:, None], order]
    c_ord = costs.astype(dtype)[order]
    a_ord = answers[rows[:, None], order]
    T = int(n_arms.max()) if N else 0
    # F before wave t: the weights of the planned arms t.. added left to
    # right (a sum in another order differs by an ulp, which moves a stop
    # only within ~1e-15 of the boundary)
    planned = np.arange(L)[None, :] < n_arms[:, None]
    w_plan = np.where(planned, w_ord, f(0.0))
    residual = np.zeros((N, T + 1), dtype)
    for t in range(T):
        acc = np.zeros(N, dtype)
        for j in range(t, L):
            acc = acc + w_plan[:, j]
        residual[:, t] = acc
    vote = np.zeros((N, K), dtype)
    voted = np.zeros((N, K), bool)
    spent = np.zeros(N, dtype)
    live = np.ones(N, bool)
    stop = n_arms.copy()
    for t in range(T):
        bel = np.where(voted, vote, empty[:, None])
        top = np.sort(bel, axis=1)
        h1, h2 = top[:, -1], top[:, -2]
        due = live & (t < n_arms)
        go = due & (residual[:, t] + h2 > h1 - f(STOP_MARGIN))
        halted = due & ~go
        stop[halted] = t
        live &= ~halted
        r = np.flatnonzero(go)
        cls = a_ord[r, t]
        vote[r, cls] += w_ord[r, t]
        voted[r, cls] = True
        spent[r] += c_ord[r, t]
    bel = np.where(voted, vote, empty[:, None])
    return np.argmax(bel, axis=1), stop, spent


class Calibration:
    """The paper's Section 3.1 estimate, from the history the benchmark
    drew: per query cluster of the history, the centroid of its embeddings
    and each arm's share of right answers (the estimate ``p``)."""

    def __init__(self, table: np.ndarray, emb: np.ndarray, clusters: np.ndarray):
        ids = np.unique(clusters)
        self.ids = ids
        self.centroids = np.stack([emb[clusters == c].mean(axis=0) for c in ids])
        self.p = np.stack([table[clusters == c].sum(axis=0) / (clusters == c).sum()
                           for c in ids])

    def nearest(self, emb: np.ndarray) -> np.ndarray:
        """Row index (into ``ids``) of each query's nearest centroid (the
        paper's query-to-cluster mapping; the squared distance less the
        query's own norm, which no centroid changes)."""
        c = self.centroids
        d = (c ** 2).sum(axis=1)[None, :] - 2.0 * (emb @ c.T)
        return np.argmin(d, axis=1)
