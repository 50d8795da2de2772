"""The work of the wave program, counted from the request, not the code.

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
