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

Every request due in the window is served; those still queued when the
window closes are drained after it, their latency counted from their due
time. Budgets are drawn uniformly from the configuration's tiers.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Optional

import numpy as np

from .pool import Pool

MIX_KEYS = {"rate_qps", "warmup_s", "why"}


def load_mix(path: pathlib.Path) -> dict:
    mix = json.loads(path.read_text())
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    return mix


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


def generate(mix: dict, pool: Pool, budgets: list, seed: int, seconds: float,
             rate: Optional[float] = None) -> Traffic:
    """The traffic of one run; ``rate`` overrides the mix's rate (the knee
    sweep)."""
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
    answers = pool.answers(pool.p_true[cid], labels, r_ans)
    payloads = np.column_stack([cid, labels, np.arange(n)]).astype(np.int64)
    return Traffic(
        n_warm=int(warm.shape[0]), offsets=offsets, payloads=payloads,
        emb=emb, budgets=budget, answers=answers,
    )
