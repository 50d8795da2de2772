"""fold_host_ms.drift: host milliseconds per routed group spent folding
returned labels: ``FeedbackLog.record_many`` plus ``FeedbackLog.apply``
(the harness's ``bench.fold`` spans over the window), over the groups
routed in it. Feedback: ``serving/feedback.py``."""


def read(ctx):
    iv = ctx.spans.get("bench.fold")
    n = len(ctx.groups)
    if iv is None or iv.size == 0 or n == 0:
        return None
    return 1e3 * float((iv[:, 1] - iv[:, 0]).sum()) / n
