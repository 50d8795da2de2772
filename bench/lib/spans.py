"""The program's own spans and counters over a window.

Two readings of the served path's host work, both named by the program
step that did it (``repro.serving.telemetry`` spans, ``thrift.*``):

* ``families``: host milliseconds per routed group of each step, the
  queue wait per request and the stalls per 1000 groups, from the
  program's span totals and scheduler counters over the window;
* ``idle_by_span``: the device's idle time inside the window, each instant
  given to the innermost host span over it (``thrift.*`` and the harness's
  ``bench.*``; ``host_other`` where none is), from a profiler trace.

This module imports nothing of the program: it reads the dictionaries
``telemetry.snapshot()`` and ``BatchScheduler.stats`` return, and trace
events.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from . import trace

PROGRAM = "thrift."
HOST_PREFIXES = (trace.SPAN_PREFIX, PROGRAM)
ADMIT = ("thrift.submit", "thrift.admit", "thrift.prefetch")
ROUTE = ("thrift.route.plan", "thrift.route.gather", "thrift.route.launch")
RETIRE = ("thrift.finalize", "thrift.retire")


def delta(before: dict, after: dict) -> Dict[str, dict]:
    """Per-name span totals closed between two snapshots."""
    out = {}
    for name, tot in after.items():
        old = before.get(name, {"count": 0, "seconds": 0.0, "slow": 0})
        d = {k: tot[k] - old[k] for k in ("count", "seconds", "slow")}
        if d["count"]:
            out[name] = d
    return out


def _per_ms(seconds: float, n: float) -> Optional[float]:
    return 1e3 * seconds / n if n > 0 else None


def families(spans: Dict[str, dict], counters: dict) -> Dict[str, Optional[float]]:
    """The host-time split of a window: ``spans`` is a :func:`delta`,
    ``counters`` the scheduler counter deltas (``requests``, ``batches``,
    ``spec_jit``, ``queue_wait_s``). None where the window has nothing to
    divide by."""
    def sec(*names):
        return sum(spans[n]["seconds"] for n in names if n in spans)

    groups, jit = counters["batches"], counters["spec_jit"]
    slow = sum(t["slow"] for t in spans.values())
    return {
        "queue_wait_ms": _per_ms(counters["queue_wait_s"], counters["requests"]),
        "admit_host_ms": _per_ms(sec(*ADMIT), groups),
        "plan_host_ms": _per_ms(sec("thrift.route.plan"), groups),
        "gather_host_ms": _per_ms(sec("thrift.route.gather"), jit),
        "launch_host_ms": _per_ms(sec("thrift.route.launch"), jit),
        "sync_wait_ms": _per_ms(sec("thrift.finalize.wait"), jit),
        "retire_host_ms": _per_ms(sec(*RETIRE), groups),
        "host_stalls": 1e3 * slow / groups if groups > 0 else None,
    }


def host_events(path: str) -> List[trace.Event]:
    """Host spans of a saved trace named ``bench.*`` or ``thrift.*``."""
    from jax.profiler import ProfileData

    out: List[trace.Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events if e.name.startswith(HOST_PREFIXES))
    return out


def window(host: Sequence[trace.Event]):
    """``(lo, hi)`` ns of the last ``bench.window`` span."""
    win = [ev for ev in host if ev[0] == trace.WINDOW]
    if not win:
        raise ValueError("trace has no bench.window span")
    return win[-1][1], win[-1][2]


def idle_by_span(raw: dict) -> Dict[str, float]:
    """Idle seconds of the window per innermost host span, averaged over
    the chips; ``raw`` as ``trace.load`` returns it, with ``host`` from
    :func:`host_events`. Unlike ``trace.reduce``'s ``idle_gaps``, every
    name is kept."""
    lo, hi = window(raw["host"])
    spans = [ev for ev in raw["host"]
             if ev[0] != trace.WINDOW and ev[2] > lo and ev[1] < hi]
    chips = sorted(raw["ops"])
    out: Dict[str, float] = {}
    for chip in chips:
        iv = np.asarray([(s, e) for _, s, e in raw["ops"][chip]], float).reshape(-1, 2)
        busy = trace.merge(trace.clip(iv, lo, hi))
        for k, v in trace.attribute(trace.complement(busy, lo, hi), spans).items():
            out[k] = out.get(k, 0.0) + v / 1e9 / len(chips)
    return out


def idle_shares(idle: Dict[str, float]) -> Dict[str, float]:
    """Percent of the idle time under ``thrift.*`` names, and under
    ``bench.*`` and ``host_other`` together."""
    total = sum(idle.values())
    if total <= 0:
        return {"program_pct": 0.0, "outside_pct": 0.0}
    prog = sum(v for k, v in idle.items() if k.startswith(PROGRAM))
    return {"program_pct": 100.0 * prog / total,
            "outside_pct": 100.0 * (total - prog) / total}


def slow_spans(host: Sequence[trace.Event], slow_s: float) -> List[list]:
    """``[name, ms, seconds into the window]`` of every ``thrift.*`` span in
    the window longer than ``slow_s``, longest first."""
    lo, hi = window(host)
    out = [[name, (e - s) / 1e6, (s - lo) / 1e9] for name, s, e in host
           if name.startswith(PROGRAM) and lo <= s < hi and (e - s) / 1e9 > slow_s]
    return sorted(out, key=lambda r: -r[1])


def by_quarter(host: Sequence[trace.Event], name: str) -> List[Optional[float]]:
    """Mean ms of the spans called ``name`` that start in each quarter of
    the window: whether a step grows as the run goes on."""
    lo, hi = window(host)
    q = (hi - lo) / 4
    sums, counts = [0.0] * 4, [0] * 4
    for n, s, e in host:
        if n == name and lo <= s < hi:
            i = min(int((s - lo) // q), 3)
            sums[i] += (e - s) / 1e6
            counts[i] += 1
    return [sums[i] / counts[i] if counts[i] else None for i in range(4)]
