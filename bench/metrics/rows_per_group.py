"""rows_per_group.{lat,qps}: requests admitted per routed group over the
window (scheduler counters ``requests`` / ``batches``). Front end:
``serving/scheduler.py``."""


def read(ctx):
    groups = ctx.counters.get("batches", 0)
    if groups <= 0:
        return None
    return ctx.counters["requests"] / groups
