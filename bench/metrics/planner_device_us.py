"""planner_device_us.drift: device microseconds per batched planner call
in the window: the summed device durations of the programs named like
``sur_greedy_scan`` (``core/selection.py``'s ``_sur_greedy_scan``, all
chips), over the calls the harness saw. Raises where calls were made and
the trace holds no such program."""
from bench.lib.trace import program_seconds

PROGRAM = "sur_greedy_scan"


def read(ctx):
    n = len(ctx.planner)
    if ctx.trace is None or n == 0:
        return None
    return 1e6 * program_seconds(ctx.trace, PROGRAM, True) / n
