"""The program-span readings (``lib/spans.py``) on synthetic spans and
traces, and ``spans.py`` end to end on the CPU at a small rate."""
import numpy as np
import pytest

from bench import spans as tool
from bench.lib import harness, spans, trace
from repro.serving import telemetry


def _raw():
    # one chip, busy [100,200) and [900,1000); the route of one group and
    # its retirement, with the program's steps nested in the harness's span
    ops = {"/device:TPU:0": [("fusion", 100, 200), ("fusion", 900, 1000)]}
    host = [("bench.window", 0, 1000),
            ("bench.route", 200, 700),
            ("thrift.route.plan", 210, 400),
            ("thrift.route.gather", 400, 450),
            ("thrift.route.launch", 450, 690),
            ("bench.finalize", 700, 850),
            ("thrift.finalize.wait", 700, 720),
            ("thrift.finalize", 720, 850),
            ("thrift.retire", 850, 880)]
    return {"ops": ops, "modules": {}, "host": host}


def test_program_spans_nested_in_bench_route_take_the_idle_time_under_them():
    raw = _raw()
    red = trace.reduce(raw)
    gaps = dict(red["idle_gaps"])
    assert gaps["thrift.route.plan"] == pytest.approx(190e-9)
    assert gaps["thrift.route.launch"] == pytest.approx(240e-9)
    # bench.route keeps only what no program span covers: [200,210) [690,700)
    assert gaps["bench.route"] == pytest.approx(20e-9)
    assert red["busy_s"] == pytest.approx(200e-9)      # unchanged by the names
    idle = spans.idle_by_span(raw)
    assert sum(idle.values()) == pytest.approx(800e-9)
    assert idle["host_other"] == pytest.approx(120e-9)   # [0,100) [880,900)
    assert "bench.finalize" not in idle                  # covered end to end
    shares = spans.idle_shares(idle)
    assert shares["program_pct"] == pytest.approx(100 * 660 / 800)
    assert shares["program_pct"] + shares["outside_pct"] == pytest.approx(100)


def test_families_from_program_totals():
    tot = lambda c, s, n=0: {"count": c, "seconds": s, "slow": n}  # noqa: E731
    before = {"thrift.route.plan": tot(5, 1.0), "thrift.gone": tot(2, 0.1)}
    after = {
        "thrift.gone": tot(2, 0.1),
        "thrift.submit": tot(10, 0.05), "thrift.admit": tot(10, 0.1),
        "thrift.prefetch": tot(4, 0.05, 1),
        "thrift.route.plan": tot(15, 1.5), "thrift.route.gather": tot(8, 0.4),
        "thrift.route.launch": tot(8, 2.0), "thrift.finalize.wait": tot(8, 0.08),
        "thrift.finalize": tot(10, 0.3), "thrift.retire": tot(10, 0.2, 1),
    }
    d = spans.delta(before, after)
    assert "thrift.gone" not in d
    assert d["thrift.route.plan"] == {"count": 10, "seconds": 0.5, "slow": 0}
    fam = spans.families(d, {"requests": 500, "batches": 10, "spec_jit": 8,
                             "queue_wait_s": 1.5})
    assert fam == pytest.approx({
        "queue_wait_ms": 3.0, "admit_host_ms": 20.0, "plan_host_ms": 50.0,
        "gather_host_ms": 50.0, "launch_host_ms": 250.0, "sync_wait_ms": 10.0,
        "retire_host_ms": 50.0, "host_stalls": 200.0,
    })
    empty = spans.families({}, {"requests": 0, "batches": 0, "spec_jit": 0,
                                "queue_wait_s": 0.0})
    assert set(empty.values()) == {None}


def test_slow_spans_and_quarters():
    host = [("bench.window", 0, 4e9), ("thrift.route.launch", 1e9, 1.2e9),
            ("thrift.prefetch", 0, 1e6), ("thrift.prefetch", 3.5e9, 3.503e9),
            ("bench.route", 1e9, 1.3e9)]
    assert spans.slow_spans(host, 0.02) == [["thrift.route.launch", 200.0, 1.0]]
    assert spans.by_quarter(host, "thrift.prefetch") == [1.0, None, None, 3.0]


def test_spans_tool_on_the_cpu():
    bench = harness.load_benchmark()
    _, config, mix = harness.load_cell("agnews-poisson", bench)
    harness.start_jax(1, require_tpu=False)
    res = tool.measure(config, dict(mix, rate_qps=300.0, warmup_s=0.3),
                       2**31 + 17, 0.8, require_tpu=False)
    assert res["correct"], res["checks"]
    assert all(v is not None for v in res["families"].values()), res["families"]
    assert 0.0 < res["route_coverage"] <= 1.0
    jit = res["counters"]["spec_jit"]
    for name in spans.ROUTE:
        assert res["spans"][name]["count"] == jit > 0
    # groups in flight at either edge of the window retire on the other side
    assert abs(res["spans"]["thrift.finalize.wait"]["count"] - jit) <= 2
    assert res["counters"]["queue_wait_s"] > 0.0
    assert telemetry._on is False              # off again after the window
    assert np.isfinite(res["p50_ms"])
