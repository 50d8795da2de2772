"""Program spans and the latency histogram of the serving path.

Spans name the host work of the served path at its real seams (admission,
planning, the speculative gather, the wave program's launch, the wait on
the device, finalization and retirement), so that every idle gap in a
device trace can be charged to the program step under it. They are off by
default and cost one global check each while off::

    from repro.serving import telemetry

    telemetry.enable(True)
    ...                               # serve traffic
    totals = telemetry.snapshot()     # {name: {"count", "seconds", "slow"}}
    telemetry.enable(False)

While on, each span enters ``jax.profiler.TraceAnnotation(name)``, so it
lands on the host plane of any active profiler session on the device
trace's clock, and adds its ``perf_counter`` duration to per-name totals:
how many spans closed, their seconds, and how many ran longer than
``SLOW_S``. The flag and the totals are process-wide, like the profiler
session they feed. Spans are leaves: no program span opens inside another,
so summing their totals never counts a second twice.

:class:`LatencyHistogram` is the scheduler's completion-latency record:
cumulative counts over fixed log-spaced buckets, everything since start in
O(1) memory.
"""
from __future__ import annotations

import math
import time
from typing import Dict, Iterable, List

import jax
import numpy as np

#: a span longer than this counts as a stall (``slow`` in the totals)
SLOW_S = 0.02

_on = False
_totals: Dict[str, List[float]] = {}      # name -> [count, seconds, slow]


class _Off:
    """The one shared span handed out while telemetry is off."""

    __slots__ = ()

    def __enter__(self):
        pass

    def __exit__(self, exc_type, exc, tb):
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("_name", "_ann", "_t0")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        tot = _totals.get(self._name)
        if tot is None:
            tot = _totals[self._name] = [0, 0.0, 0]
        tot[0] += 1
        tot[1] += dt
        tot[2] += dt > SLOW_S


def enable(on: bool) -> None:
    """Turn program spans on or off for the whole process. Totals persist
    across toggles; take the difference of two :func:`snapshot` calls to
    read an interval."""
    global _on
    _on = bool(on)


def span(name: str):
    """A context manager timing one step of the served path under ``name``
    (``thrift.*``). Off: the same shared no-op object on every call."""
    if not _on:
        return _OFF
    return _Span(name)


def snapshot() -> Dict[str, Dict[str, float]]:
    """Per-name totals since start (or :func:`reset`): ``count`` spans,
    their ``seconds``, and ``slow`` spans over ``SLOW_S``."""
    return {name: {"count": int(c), "seconds": float(s), "slow": int(n)}
            for name, (c, s, n) in _totals.items()}


def reset() -> None:
    """Forget every total."""
    _totals.clear()


class LatencyHistogram:
    """Completion latencies as cumulative counts over fixed log-spaced
    buckets: 1 us to 1000 s, each bucket 5 % wider than the one before,
    plus one bucket below and one above. Covers everything recorded since
    start in O(1) memory; percentiles read bucket upper edges (the exact
    maximum for the top bucket, and never above the maximum), so they
    overstate a latency by at most 5 %.

    ``add`` copies into a fixed staging buffer and bins it when full, so
    the retire path pays a slice copy, not a binning pass, per group."""

    LO_S = 1e-6
    HI_S = 1e3
    RATIO = 1.05
    #: upper edges of every bucket but the open top one
    EDGES = LO_S * RATIO ** np.arange(
        math.ceil(math.log(HI_S / LO_S) / math.log(RATIO)) + 1)
    STAGE = 8192

    __slots__ = ("_counts", "_total_s", "_max_s", "_stage", "_staged")

    def __init__(self):
        self._counts = np.zeros(self.EDGES.size + 1, np.int64)
        self._total_s = 0.0
        self._max_s = -math.inf
        self._stage = np.empty(self.STAGE, np.float64)
        self._staged = 0

    def add(self, latencies: np.ndarray) -> None:
        n = latencies.shape[0]
        if self._staged + n > self.STAGE:
            self._fold()
            if n > self.STAGE:
                self._bin(latencies)
                return
        self._stage[self._staged:self._staged + n] = latencies
        self._staged += n

    def _fold(self) -> None:
        if self._staged:
            self._bin(self._stage[:self._staged])
            self._staged = 0

    def _bin(self, latencies: np.ndarray) -> None:
        idx = np.searchsorted(self.EDGES, latencies, side="left")
        self._counts += np.bincount(idx, minlength=self._counts.size)
        self._total_s += float(latencies.sum())
        self._max_s = max(self._max_s, float(latencies.max()))

    @classmethod
    def pooled(cls, hists: Iterable["LatencyHistogram"]) -> "LatencyHistogram":
        out = cls()
        for h in hists:
            h._fold()
            out._counts += h._counts
            out._total_s += h._total_s
            out._max_s = max(out._max_s, h._max_s)
        return out

    def percentile(self, q: float) -> float:
        """Upper edge of the bucket holding the nearest-rank ``q``-th
        percentile (``q`` in [0, 100])."""
        self._fold()
        n = int(self._counts.sum())
        rank = max(1, math.ceil(q / 100.0 * n))
        i = int(np.searchsorted(np.cumsum(self._counts), rank, side="left"))
        edge = self.EDGES[i] if i < self.EDGES.size else self._max_s
        return float(min(edge, self._max_s))

    def summary(self) -> Dict[str, float]:
        """``count``, ``p50_s``, ``p99_s``, ``mean_s``, ``max_s`` of
        everything recorded; ``{"count": 0}`` when empty."""
        self._fold()
        n = int(self._counts.sum())
        if n == 0:
            return {"count": 0}
        return {
            "count": n,
            "p50_s": self.percentile(50),
            "p99_s": self.percentile(99),
            "mean_s": self._total_s / n,
            "max_s": float(self._max_s),
        }
