"""wave_device_us.{lat,qps}: device microseconds of the wave program per
dispatched jit-plane group: the summed device durations of the program's
trace events (all chips), divided by the groups dispatched in the window.
The program is the XLA module of ``serving/router.py``'s ``_wave_scan``."""
from bench.lib.trace import program_seconds

PROGRAM = "wave_scan"


def read(ctx):
    n = sum(1 for kind, _, _ in ctx.groups if kind == "jit")
    if ctx.trace is None or n == 0:
        return None
    return 1e6 * program_seconds(ctx.trace, PROGRAM, True) / n
