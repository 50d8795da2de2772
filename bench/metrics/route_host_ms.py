"""route_host_ms.{lat,qps}: host milliseconds per routed group spent in
``ThriftRouter.begin_route`` (planning tables, response gather, dispatch)
plus ``PendingRoute.step`` (the compacting reference plane's waves), from
the harness's spans over the window. Router host plane:
``serving/router.py``, ``serving/engine.py``, ``serving/plans.py``."""


def read(ctx):
    n = len(ctx.groups)
    if n == 0:
        return None
    total = 0.0
    for name in ("bench.route", "bench.step"):
        iv = ctx.spans.get(name)
        if iv is not None and iv.size:
            total += float((iv[:, 1] - iv[:, 0]).sum())
    return 1e3 * total / n
