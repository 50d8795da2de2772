"""Latency and rate arithmetic of the end-to-end metrics.

* Latency is a request's completion time minus its due time (the open
  loop's schedule, not when the generator got round to sending it), so a
  stall is charged to every request that waited behind it.
* ``p50_ms`` and ``p99_ms`` are nearest-rank percentiles over every request
  due in the window, stragglers drained after the window included. A
  request that never completed counts as infinitely late.
* ``served_qps`` is the number of completions inside the window divided by
  the window's length.
"""
from __future__ import annotations

import math

import numpy as np


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("no values")
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])


def latency_ms(latency_s: np.ndarray, done: np.ndarray) -> dict:
    """p50/p99 in ms over all requests due in the window."""
    lat = np.where(np.asarray(done, bool), np.asarray(latency_s, np.float64), np.inf)
    return {"p50_ms": 1e3 * percentile(lat, 50), "p99_ms": 1e3 * percentile(lat, 99)}


def served_qps(completed_in_window: int, window_s: float) -> float:
    return float(completed_in_window) / float(window_s)
