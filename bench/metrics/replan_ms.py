"""replan_ms.drift: host milliseconds per drift replan:
``PlanService.replan_stale`` (the harness's ``bench.replan`` spans over
the window, the batched planner's dispatch and its wait included), over
the calls in it. Planner: ``serving/plans.py`` -> ``core/selection.py``."""


def read(ctx):
    iv = ctx.spans.get("bench.replan")
    if iv is None or iv.size == 0:
        return None
    return 1e3 * float((iv[:, 1] - iv[:, 0]).sum()) / iv.shape[0]
