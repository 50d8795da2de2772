"""From a profiler trace to device busy time, idle share and idle gaps.

The raw material is a list of events ``(name, start_ns, end_ns)``:

* device operations, one list per chip (the ``XLA Ops`` line of each
  ``/device:TPU:n`` plane, or its programs where a trace has no op line),
  and the programs that contain them (the ``XLA Modules`` line);
* the harness's own host spans, named ``bench.*`` and written with
  ``jax.profiler.TraceAnnotation`` on the same clock; ``bench.window``
  marks the measured window.

``reduce`` turns them into:

* ``busy_s``: the union of the intervals in which an operation ran on a
  chip, inside the window, averaged over the chips;
* ``window_s`` and ``idle_pct`` = 100 (1 - busy / window);
* ``programs``: device seconds per program name, summed over the chips;
* ``device_ops``: the ten operations that took most device time;
* ``idle_gaps``: the idle time, attributed to what the host was doing in it
  (the innermost ``bench.*`` span covering each instant, ``host_other``
  where none does), the ten largest.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

Event = Tuple[str, float, float]
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """Raw events of a saved trace: ``{"ops": {chip: [Event]}, "modules":
    {chip: [Event]}, "host": [Event]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = ops.setdefault(plane.name, [])
                elif line.name == MODULES_LINE:
                    dest = modules.setdefault(plane.name, [])
                else:
                    continue
                dest.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                )
    for chip, evs in modules.items():
        # a chip whose trace has no op line is busy while its programs run
        ops.setdefault(chip, list(evs))
    return {"ops": ops, "modules": modules, "host": host}


def merge(intervals: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of (n, 2) [start, end) intervals."""
    if intervals.size == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(iv.shape[0], bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(new)
    stops = ends[np.r_[last[1:] - 1, iv.shape[0] - 1]]
    return np.column_stack([starts, stops])


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if intervals.size == 0:
        return np.zeros((0, 2))
    iv = np.column_stack([np.maximum(intervals[:, 0], lo),
                          np.minimum(intervals[:, 1], hi)])
    return iv[iv[:, 1] > iv[:, 0]]


def complement(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Idle intervals of [lo, hi) given sorted disjoint busy intervals."""
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def innermost(spans: Sequence[Event]) -> List[Event]:
    """Flatten properly nested spans into disjoint pieces, each named by
    the innermost span covering it."""
    out: List[Event] = []
    stack: List[list] = []          # [name, end, covered_until]

    def emit_until(t: float) -> None:
        # close every open span that ends by t, emitting its tail
        while stack and stack[-1][1] <= t:
            name, end, cur = stack.pop()
            if end > cur:
                out.append((name, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(spans, key=lambda ev: (ev[1], -ev[2])):
        emit_until(s)
        if stack and s > stack[-1][2]:
            out.append((stack[-1][0], stack[-1][2], s))
        if stack:
            stack[-1][2] = max(stack[-1][2], e)
        stack.append([name, e, s])
    emit_until(float("inf"))
    return [ev for ev in out if ev[2] > ev[1]]


def _covered(gaps: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Total gap length in (-inf, x] for each x (gaps sorted, disjoint)."""
    if gaps.size == 0:
        return np.zeros_like(x, dtype=float)
    lens = gaps[:, 1] - gaps[:, 0]
    before = np.concatenate([[0.0], np.cumsum(lens)])
    i = np.searchsorted(gaps[:, 0], x, side="right") - 1
    inside = np.clip(x - gaps[np.maximum(i, 0), 0], 0.0, lens[np.maximum(i, 0)])
    return np.where(i < 0, 0.0, before[np.maximum(i, 0)] + inside)


def attribute(gaps: np.ndarray, spans: Sequence[Event]) -> Dict[str, float]:
    """Idle ns per host activity: the innermost span over each idle
    instant; ``host_other`` for idle time no span covers."""
    out: Dict[str, float] = {}
    pieces = innermost(spans)
    if pieces:
        s = np.asarray([p[1] for p in pieces], float)
        e = np.asarray([p[2] for p in pieces], float)
        ov = _covered(gaps, e) - _covered(gaps, s)
        for (name, _, _), v in zip(pieces, ov):
            if v > 0:
                out[name] = out.get(name, 0.0) + float(v)
    total = float((gaps[:, 1] - gaps[:, 0]).sum()) if gaps.size else 0.0
    rest = total - sum(out.values())
    if rest > 0:
        out["host_other"] = rest
    return out


def _strip(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _top(totals: Dict[str, float], n: int = 10) -> list:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(raw: dict) -> dict:
    """Busy time, idle share, per-program time and gaps of one traced window."""
    win = [ev for ev in raw["host"] if ev[0] == WINDOW]
    if not win:
        raise ValueError("trace has no bench.window span")
    lo, hi = win[-1][1], win[-1][2]
    window_ns = hi - lo
    spans = [ev for ev in raw["host"] if ev[0] != WINDOW and ev[2] > lo and ev[1] < hi]
    chips = sorted(raw["ops"])
    busy_ns, gap_ns = [], {}
    op_ns: Dict[str, float] = {}
    for chip in chips:
        evs = raw["ops"][chip]
        iv = np.asarray([(s, e) for _, s, e in evs], float).reshape(-1, 2)
        busy = merge(clip(iv, lo, hi))
        busy_ns.append(float((busy[:, 1] - busy[:, 0]).sum()))
        for k, v in attribute(complement(busy, lo, hi), spans).items():
            gap_ns[k] = gap_ns.get(k, 0.0) + v
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[name] = op_ns.get(name, 0.0) + d
    prog_ns: Dict[str, float] = {}
    for chip in sorted(raw["modules"]):
        for name, s, e in raw["modules"][chip]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = _strip(name)
                prog_ns[key] = prog_ns.get(key, 0.0) + d
    n = max(len(chips), 1)
    busy_s = sum(busy_ns) / n / 1e9
    window_s = window_ns / 1e9
    return {
        "chips": len(chips),
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None,
        "programs": {k: v / 1e9 for k, v in prog_ns.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in _top(op_ns)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in _top(gap_ns)],
        "spans": spans,
    }


def program_seconds(reduced: dict, name: str, dispatched: bool) -> float:
    """Device seconds of the programs whose module name holds ``name``.
    Raises where the harness saw such a program ``dispatched`` in the window
    and the trace holds none of that name: a renamed module must not leave
    its metrics silent."""
    t = sum(v for k, v in reduced["programs"].items() if name in k)
    if t <= 0 and dispatched:
        raise LookupError(f"programs dispatched but none named like {name!r} in "
                          f"the trace: {sorted(reduced['programs'])}")
    return t
