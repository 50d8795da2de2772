"""One general generator over the traffic mixes in ``bench/traffic/*.json``.

A mix is data: its offered rate and how long the warm-up phase runs. The
generator turns (mix, pool, budget tiers, seed, window length) into
everything the run will send: open-loop Poisson due times, payloads,
embeddings, budgets and the answer table. The same seed gives the same
traffic.

Keys of a mix:

* ``rate_qps``: offered load in queries per second, fixed per cell.
* ``warmup_s``: seconds of the same traffic served (and drained) before the
  window, so every shape the window uses is warm.
* ``why``: one line on what the mix is for.

Optional keys, for a deployment with online feedback:

* ``labels``: ``"on_completion"`` returns each request's true label to the
  program as soon as its answer is read (warm-up included).
* ``drift_period_s``, ``drift_clusters``, ``drift_p``, ``drift_tier``: the
  truth moves at window times ``k * drift_period_s`` (k = 1, 2, ...). Odd
  events draw, from the seed, ``drift_clusters`` of the pool's clusters and
  set the true accuracy of each one's drift arms to ``drift_p``; even
  events restore every cluster. A cluster's drift arms are the set the
  benchmark's reference SurGreedy plans for it at ``drift_tier`` under the
  benchmark's calibration (``drift_arm_sets``), so the traffic stays a
  function of the seed and the configuration alone. The warm-up never
  drifts.

Every request due in the window is served; those still queued when the
window closes are drained after it, their latency counted from their due
time. Budgets are drawn uniformly from the configuration's tiers. Each
request's answers are drawn under the truth in force at its due time.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Optional

import numpy as np

from .pool import Pool

MIX_KEYS = {"rate_qps", "warmup_s", "why", "labels", "drift_period_s",
            "drift_clusters", "drift_p", "drift_tier"}
DRIFT_KEYS = ("drift_period_s", "drift_clusters", "drift_p", "drift_tier")
LABELS = (None, "on_completion")


def load_mix(path: pathlib.Path) -> dict:
    mix = json.loads(path.read_text())
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    if mix.get("labels") not in LABELS:
        raise ValueError(f"{path}: labels must be one of {LABELS[1:]}")
    given = [k for k in DRIFT_KEYS if k in mix]
    if given and len(given) != len(DRIFT_KEYS):
        raise ValueError(f"{path}: drift needs all of {DRIFT_KEYS}")
    return mix


def drifts(mix: dict) -> bool:
    return "drift_period_s" in mix


def drift_arm_sets(mix: dict, p_by_cluster: np.ndarray, costs: np.ndarray,
                   num_classes: int) -> np.ndarray:
    """(C, L) bool: the arms the reference SurGreedy (``planref``) plans for
    each cluster row at the mix's ``drift_tier``, under the estimate
    ``p_by_cluster`` (the benchmark's calibration)."""
    from . import planref

    tier = float(mix["drift_tier"])
    return np.stack([planref.sur_greedy(xi, costs, tier)
                     for xi in planref.xis_for(p_by_cluster, num_classes)])


@dataclasses.dataclass
class Traffic:
    """Everything one run sends. Rows ``[:n_warm]`` are the warm-up phase,
    rows ``[n_warm:]`` the measured window; offsets are seconds from the
    start of their own phase."""

    n_warm: int
    offsets: np.ndarray        # (N,) due time within its phase
    payloads: np.ndarray       # (N, 3) int64: cluster, label, query index
    emb: np.ndarray            # (N, d)
    budgets: np.ndarray        # (N,) USD
    answers: np.ndarray        # (L, N) each arm's answer to each query
    labels: bool = False       # true labels go back to the program
    # drifted window segments: (start_s, end_s, cluster ids) each
    drift: list = dataclasses.field(default_factory=list)

    @property
    def n(self) -> int:
        return int(self.offsets.shape[0])


def poisson_offsets(rate: float, seconds: float, rng: np.random.Generator):
    """Due times of an open-loop Poisson stream in ``[0, seconds)``, held to
    its expected count: ``round(rate * seconds)`` arrivals, uniform order
    statistics (a Poisson process given its count), so every seed offers
    the same amount of work in another order."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n)) if n > 0 else np.zeros(0)


def drift_events(mix: dict, seconds: float) -> int:
    """Drift events (drifts and restores) inside a window of ``seconds``."""
    if not drifts(mix):
        return 0
    return int(np.ceil(seconds / float(mix["drift_period_s"]))) - 1


def drift_segments(mix: dict, num_clusters: int, seconds: float,
                   rng: np.random.Generator) -> list:
    """``[(start_s, end_s, clusters)]``: the drifted stretches of a window
    of ``seconds``. Event k (k = 1, 2, ...) falls at ``k * period``; odd
    events draw the clusters, even ones restore. Draws are made in event
    order, so a longer window keeps a shorter one's drifts."""
    period = float(mix["drift_period_s"])
    out = []
    k = 1
    while k * period < seconds:
        if k % 2 == 1:
            drawn = np.sort(rng.choice(num_clusters, int(mix["drift_clusters"]),
                                       replace=False))
            out.append((k * period, min((k + 1) * period, seconds), drawn))
        k += 1
    return out


def generate(mix: dict, pool: Pool, budgets: list, seed: int, seconds: float,
             rate: Optional[float] = None,
             drift_arms: Optional[np.ndarray] = None) -> Traffic:
    """The traffic of one run; ``rate`` overrides the mix's rate (the knee
    sweep). A drifting mix needs ``drift_arms``, from :func:`drift_arm_sets`."""
    rate = float(mix["rate_qps"] if rate is None else rate)
    ss = np.random.SeedSequence(int(seed))
    r_arr, r_q, r_b, r_ans = (np.random.default_rng(s) for s in ss.spawn(4))
    warm = poisson_offsets(rate, float(mix.get("warmup_s", 0.0)), r_arr)
    win = poisson_offsets(rate, float(seconds), r_arr)
    offsets = np.concatenate([warm, win])
    n = offsets.shape[0]
    cid, emb, labels = pool.queries(n, r_q)
    tiers = np.asarray(budgets, np.float64)
    budget = tiers[r_b.integers(tiers.size, size=n)]
    truth = pool.p_true[cid]
    segments = []
    if drifts(mix):
        if drift_arms is None:
            raise ValueError("a drifting mix needs the clusters' drift arms")
        r_drift = np.random.default_rng(ss.spawn(1)[0])
        segments = drift_segments(mix, pool.num_clusters, float(seconds), r_drift)
        n_warm = warm.shape[0]
        for start, end, drawn in segments:
            lo, hi = n_warm + np.searchsorted(win, [start, end])
            rows = lo + np.flatnonzero(np.isin(cid[lo:hi], drawn))
            hit = drift_arms[cid[rows]]
            truth[rows] = np.where(hit, float(mix["drift_p"]), truth[rows])
    answers = pool.answers(truth, labels, r_ans)
    payloads = np.column_stack([cid, labels, np.arange(n)]).astype(np.int64)
    return Traffic(
        n_warm=int(warm.shape[0]), offsets=offsets, payloads=payloads,
        emb=emb, budgets=budget, answers=answers,
        labels=mix.get("labels") == "on_completion", drift=segments,
    )
