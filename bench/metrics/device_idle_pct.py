"""device_idle_pct.{lat,qps}: 100 (1 - busy / window), busy being the
union of the intervals in which an operation ran on a chip inside the
traced window; the mean over the chips of a four-chip cell."""


def read(ctx):
    if ctx.trace is None or ctx.trace["idle_pct"] is None:
        return None
    return ctx.trace["idle_pct"]
