"""The work of the device programs, counted from the request, not the code.

The wave program:

One routed group is B requests, a plan of T waves and K classes. The
program has to read, once, per (wave, request) cell: the arm scheduled
(int32), its answer (int32), its log weight (float64), the Prop. 4
residual (float64), the failover source wave (int32) and whether the slot
has an arm (bool); per request the no-vote belief (float64). It has to
write, once, per request: the stop wave (int32), the prediction (int32)
and the K class beliefs (float64). Those are the reference's dtypes.
Padding to compile buckets, a layout or a precision change leaves the
count as it is: a faster program is judged against the same bytes.
"""
from __future__ import annotations

CELL_IN = 4 + 4 + 8 + 8 + 4 + 1     # arm, answer, weight, residual, src, valid
ROW_IN = 8                          # no-vote belief
ROW_OUT = 4 + 4                     # stop wave, prediction
BELIEF = 8                          # per class


def wave_bytes(B: int, T: int, K: int) -> int:
    """HBM bytes one wave-program call over an unpadded (B, T, K) group
    must move."""
    B, T, K = int(B), int(T), int(K)
    return B * (T * CELL_IN + ROW_IN + ROW_OUT + K * BELIEF)


def planner_bytes(G: int, thetas, L: int, K: int) -> int:
    """HBM bytes one batched planner call over ``G`` (cluster, budget)
    groups must move, by the paper's SurGreedy with Monte Carlo ``xi``: it
    scores arm sets on ``theta_g`` sampled realisations of the L arms'
    answers per group, so each group's (theta_g, L) answer table (int32
    class ids) is read at least once; and it reads each group's estimate
    and the prices (float64, L each) and writes its chosen set (int32, L)
    and the ``xi`` of its three candidates (float64). ``K`` sets no byte:
    a realisation's answer is one class id whatever K is. Counted from the
    unpadded groups and their own ``theta``: compile buckets count
    nothing."""
    thetas = [int(t) for t in thetas]
    if len(thetas) != int(G):
        raise ValueError("one theta per group")
    L = int(L)
    return sum(t * L * 4 for t in thetas) + int(G) * (L * 8 * 2 + L * 4 + 3 * 8)
