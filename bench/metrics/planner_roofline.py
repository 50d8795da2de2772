"""planner_roofline.drift: the batched planner's share of its HBM
roofline. The bytes it must move are counted from each call's unpadded
groups and their Monte Carlo sample counts by ``lib/work.py``
(``planner_bytes``: SurGreedy reads every sampled answer at least once);
the least time for them is bytes over the chip's HBM bandwidth
(``lib/peaks.py``); the share is that time over the device time of the
programs named like ``sur_greedy_scan`` in the trace (all chips). Bytes
bound this program: scoring an arm set on a realisation is a few adds and
compares per answer read, with no matrix product for the chip's MXU."""
from bench.lib.trace import program_seconds
from bench.lib.work import planner_bytes

PROGRAM = "sur_greedy_scan"


def read(ctx):
    if ctx.trace is None or not ctx.peaks or not ctx.planner:
        return None
    moved = sum(planner_bytes(len(th), th, ctx.num_arms, ctx.num_classes)
                for th in ctx.planner)
    t = program_seconds(ctx.trace, PROGRAM, True)
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / t
