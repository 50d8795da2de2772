"""Program spans of the served path (``repro.serving.telemetry``), the
``queue_wait_s`` counter and the scheduler's latency histogram.

Off, a span is one shared no-op object and never touches the profiler; on,
the spans of one group are disjoint leaves whose counts follow the groups
served, and what the router returns is the same either way.
"""
import time

import numpy as np
import pytest

from repro.serving import BatchScheduler, telemetry
from repro.serving.telemetry import LatencyHistogram
from tests.test_scheduler_continuous import _make_pool

ROUTE_SPANS = ("thrift.route.plan", "thrift.route.gather",
               "thrift.route.launch", "thrift.finalize.wait")


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.enable(False)
    telemetry.reset()
    yield
    telemetry.enable(False)
    telemetry.reset()


class _Recorded:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that logs each enter
    and exit, in order."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def _serve(router, qemb, budget, B, max_batch):
    sched = BatchScheduler(router, max_batch=max_batch, max_wait_s=0.0)
    blk = sched.submit_many(np.arange(B), qemb[:B], budget)
    sched.drain()
    return sched, blk


def _budget(engine):
    return float(np.quantile(engine.costs, 0.6)) * 2


def test_off_span_is_one_shared_object_and_serving_records_nothing(monkeypatch):
    def refuse(name):
        raise AssertionError(f"TraceAnnotation({name!r}) entered while off")

    monkeypatch.setattr(telemetry.jax.profiler, "TraceAnnotation", refuse)
    assert telemetry.span("a") is telemetry.span("b")
    engine, router, qemb = _make_pool(B=64)
    sched, blk = _serve(router, qemb, _budget(engine), 64, 8)
    assert blk.done() and sched.stats["spec_jit"] == 8
    assert telemetry.snapshot() == {}


def test_on_spans_follow_the_jit_groups_and_never_overlap(monkeypatch):
    monkeypatch.setattr(_Recorded, "log", [])
    monkeypatch.setattr(telemetry.jax.profiler, "TraceAnnotation", _Recorded)
    telemetry.enable(True)
    engine, router, qemb = _make_pool(B=64)
    sched, blk = _serve(router, qemb, _budget(engine), 64, 8)
    telemetry.enable(False)
    st, snap = sched.stats, telemetry.snapshot()
    assert blk.done() and st["spec_jit"] == 8
    for name in ROUTE_SPANS:
        assert snap[name]["count"] == 8, name
    assert snap["thrift.finalize"]["count"] == 8
    assert snap["thrift.retire"]["count"] == 8
    assert snap["thrift.admit"]["count"] == st["flushes"]
    assert snap["thrift.submit"]["count"] == 1
    assert all(name.startswith("thrift.") for name in snap)
    assert all(t["seconds"] >= 0.0 for t in snap.values())
    # leaves: every enter is closed by its own exit before the next enter
    log = _Recorded.log
    assert len(log) == 2 * sum(t["count"] for t in snap.values())
    for (e, a), (x, b) in zip(log[::2], log[1::2]):
        assert (e, x, a) == ("enter", "exit", b)


def test_on_reference_group_steps_under_its_own_span(monkeypatch):
    monkeypatch.setattr(_Recorded, "log", [])
    monkeypatch.setattr(telemetry.jax.profiler, "TraceAnnotation", _Recorded)
    telemetry.enable(True)
    engine, router, qemb = _make_pool(B=32, metered=True)
    sched, blk = _serve(router, qemb, _budget(engine), 32, 16)
    snap = telemetry.snapshot()
    assert blk.done() and sched.stats["spec_reference"] == 2
    assert snap["thrift.route.step"]["count"] >= 2
    assert snap["thrift.route.plan"]["count"] == 2
    assert snap["thrift.finalize"]["count"] == 2
    assert "thrift.route.launch" not in snap and "thrift.finalize.wait" not in snap
    log = _Recorded.log
    for (e, a), (x, b) in zip(log[::2], log[1::2]):
        assert (e, x, a) == ("enter", "exit", b)


def test_slow_spans_are_counted(monkeypatch):
    monkeypatch.setattr(telemetry, "SLOW_S", 0.0)
    telemetry.enable(True)
    with telemetry.span("thrift.test"):
        pass
    tot = telemetry.snapshot()["thrift.test"]
    assert tot["count"] == 1 and tot["slow"] == 1


def test_outputs_identical_with_telemetry_on_and_off():
    out = []
    for on in (False, True):
        telemetry.enable(on)
        engine, router, qemb = _make_pool(B=96)
        _, blk = _serve(router, qemb, _budget(engine), 96, 24)
        out.append(blk)
    off, on = out
    np.testing.assert_array_equal(off.predictions, on.predictions)
    np.testing.assert_array_equal(off.costs, on.costs)
    np.testing.assert_array_equal(off.stop_waves, on.stop_waves)
    np.testing.assert_array_equal(off.clusters, on.clusters)


def test_queue_wait_counts_admission_minus_arrival():
    engine, router, qemb = _make_pool(B=32)
    sched = BatchScheduler(router, max_batch=16, max_wait_s=0.0)
    arrival = time.monotonic() - 1.0               # every row waited >= 1 s
    blk = sched.submit_many(np.arange(32), qemb[:32], _budget(engine),
                            arrival_s=arrival)
    sched.drain()
    wait = sched.stats["queue_wait_s"]
    assert 32 * 1.0 <= wait <= float(blk.latencies_s.sum())


def test_latency_histogram_percentiles_within_a_bucket():
    rng = np.random.default_rng(0)
    lat = np.exp(rng.uniform(np.log(2e-6), np.log(500.0), 20000))
    h = LatencyHistogram()
    h.add(lat[:9000])                              # larger than the stage
    for part in np.array_split(lat[9000:], 7):     # stage fills and folds
        h.add(part)
    got = h.summary()
    srt = np.sort(lat)
    for q, key in ((50, "p50_s"), (99, "p99_s")):
        exact = srt[int(np.ceil(q / 100 * lat.size)) - 1]   # nearest rank
        assert exact <= got[key] <= exact * LatencyHistogram.RATIO
    assert got["count"] == lat.size
    assert got["mean_s"] == pytest.approx(lat.mean(), rel=1e-12)
    assert got["max_s"] == lat.max()
    edges = LatencyHistogram.EDGES
    assert edges[0] == LatencyHistogram.LO_S and edges[-1] >= LatencyHistogram.HI_S
    assert np.all(edges[1:] / edges[:-1] <= LatencyHistogram.RATIO + 1e-12)


def test_latency_histogram_pools_and_clamps():
    a, b = LatencyHistogram(), LatencyHistogram()
    assert a.summary() == {"count": 0}
    a.add(np.array([1e-3, 2e-3]))
    b.add(np.array([5e-7, 2e4]))                   # below and above the range
    pooled = LatencyHistogram.pooled([a, b]).summary()
    assert pooled["count"] == 4
    assert pooled["max_s"] == 2e4 and pooled["p99_s"] == 2e4
    one = LatencyHistogram()
    one.add(np.array([3e-3]))
    assert one.summary()["p50_s"] == 3e-3          # never above the maximum
