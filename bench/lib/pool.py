"""The deployment's arm pool and its answers: the paper's Eq. 1 error model.

A pool has query clusters and L arms whose true success probability varies
by cluster; stronger arms cost more (FLOP-proportional pricing with a
spread, the regime of the paper's Table 4). An arm answers a query of
cluster c with its label with probability ``p_true[c, arm]``, and otherwise
with one of the K-1 wrong classes, uniformly (Eq. 1).

Everything is drawn once, up front, from seeds: the pool from the
configuration's ``pool_seed``, the calibration history from its
``history_seed``, and queries and answers from the run's ``--seed``. Each
arm's answer to each query is fixed in a table before any plane asks, so
the served planes and the reference see the same answers and can be
compared request by request.
"""
from __future__ import annotations

import numpy as np


class Pool:
    """Clusters, arms, prices and true success probabilities of a pool."""

    def __init__(self, *, arms: int, classes: int, clusters: int,
                 emb_dim: int, skill_spread: float, base_low: float,
                 base_high: float, pool_seed: int, **_unused):
        self.num_arms = int(arms)
        self.num_classes = int(classes)
        self.num_clusters = int(clusters)
        self.emb_dim = int(emb_dim)
        rng = np.random.default_rng(int(pool_seed))
        centers = rng.normal(0.0, 1.0, (self.num_clusters, self.emb_dim))
        self.centers = centers / np.linalg.norm(centers, axis=1, keepdims=True)
        base = np.linspace(base_low, base_high, self.num_arms)
        skew = rng.normal(0.0, skill_spread, (self.num_clusters, self.num_arms))
        self.p_true = np.clip(base[None, :] + skew, 0.05, 0.995)
        flops = np.geomspace(1.0, 600.0, self.num_arms)
        self.costs = flops * 3.5e-7 * rng.uniform(0.8, 1.25, self.num_arms)

    def queries(self, n: int, rng: np.random.Generator):
        """``n`` queries: (cluster ids, embeddings (n, d), labels)."""
        cid = rng.integers(self.num_clusters, size=n)
        emb = self.centers[cid] + rng.normal(0.0, 0.08, (n, self.emb_dim))
        labels = rng.integers(self.num_classes, size=n)
        return cid, emb, labels

    def answers(self, p: np.ndarray, labels: np.ndarray,
                rng: np.random.Generator) -> np.ndarray:
        """(L, n) class ids: arm l answers query i under truth ``p[i, l]``."""
        n = labels.shape[0]
        K = self.num_classes
        u = rng.random((2, self.num_arms, n))
        hit = u[0] < p.T
        wrong = np.minimum((u[1] * (K - 1)).astype(np.int64), K - 2)
        return np.where(hit, labels[None, :], (labels[None, :] + 1 + wrong) % K)

    def history(self, n: int, seed: int):
        """Calibration history: (correctness table (n, L), embeddings, clusters)."""
        rng = np.random.default_rng(int(seed))
        cid, emb, labels = self.queries(n, rng)
        ans = self.answers(self.p_true[cid], labels, rng)
        return (ans == labels[None, :]).T.astype(np.float64), emb, cid
