"""wave_roofline.{lat,qps}: the wave program's share of its HBM roofline.
The bytes it must move are counted from each dispatched group's unpadded
(B, T, K) by ``lib/work.py``; the least time for them is bytes over the
chip's HBM bandwidth (``lib/peaks.py``); the share is that time over the
program's device time in the trace (the module of ``_wave_scan``, all
chips). Bytes bound this program: its arithmetic is a few adds and
compares per byte."""
from bench.lib.trace import program_seconds
from bench.lib.work import wave_bytes

PROGRAM = "wave_scan"


def read(ctx):
    jit = [(B, T) for kind, B, T in ctx.groups if kind == "jit"]
    if ctx.trace is None or not ctx.peaks or not jit:
        return None
    moved = sum(wave_bytes(B, T, ctx.num_classes) for B, T in jit)
    t = program_seconds(ctx.trace, PROGRAM, True)
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / t
