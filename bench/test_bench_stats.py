"""Latency and rate arithmetic, the peaks table and the work count."""
import numpy as np
import pytest

from bench.lib import stats, work
from bench.lib.peaks import peaks


def test_nearest_rank_percentiles_over_every_request():
    # 200 requests due in the window: 198 quick, two drained stragglers
    lat = np.full(200, 0.004)
    lat[:100] = 0.002
    lat[-2:] = [0.5, 0.9]
    done = np.ones(200, bool)
    got = stats.latency_ms(lat, done)
    assert got["p50_ms"] == pytest.approx(2.0)      # rank 100 of 200
    assert got["p99_ms"] == pytest.approx(4.0)      # rank 198: no straggler yet
    lat[-3] = 0.7
    assert stats.latency_ms(lat, done)["p99_ms"] == pytest.approx(500.0)


def test_a_request_that_never_completed_counts_as_late():
    lat = np.full(100, 0.001)
    done = np.ones(100, bool)
    done[:2] = False
    assert stats.latency_ms(lat, done)["p99_ms"] == np.inf


def test_served_qps_counts_completions_inside_the_window():
    # completion log: 5000 inside a 2.5 s window; the drained backlog is
    # not passed in and does not count
    assert stats.served_qps(5000, 2.5) == pytest.approx(2000.0)


def test_peaks_are_known_for_v5e_and_unknown_kinds_fail():
    p = peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v99")


@pytest.mark.parametrize("B,T,K", [(1, 1, 4), (37, 5, 4), (64, 12, 4), (64, 9, 77)])
def test_wave_bytes_depends_on_the_unpadded_group_only(B, T, K):
    from repro.core.mc import bucket_size

    want = B * (T * 29 + 8 + 8 + 8 * K)
    assert work.wave_bytes(B, T, K) == want
    # the count takes no bucket: padding to the program's compile buckets
    # would count more bytes than the group holds
    Bp, Tp = bucket_size(B, 8), bucket_size(T, 4)
    if (Bp, Tp) != (B, T):
        assert work.wave_bytes(Bp, Tp, K) > want


def test_roofline_reader_counts_recorded_groups_not_buckets():
    from bench.lib.harness import Context, metric_reader

    read = metric_reader("wave_roofline.lat")
    trace = {"programs": {"jit__wave_scan_core": 1e-3}}
    ctx = Context(variant="lat", counters={}, groups=[("jit", 37, 5), ("jit", 64, 9)],
                  spans={}, num_classes=4, trace=trace,
                  peaks=peaks("TPU v5 lite"))
    moved = work.wave_bytes(37, 5, 4) + work.wave_bytes(64, 9, 4)
    assert read(ctx) == pytest.approx(100.0 * moved / 819e9 / 1e-3)
    # reference-plane groups have no wave program: nothing to read
    ctx.groups = [("reference", 12, 5)]
    assert read(ctx) is None


@pytest.mark.parametrize("name", ["wave_roofline.qps", "wave_device_us.qps"])
def test_wave_readers_refuse_a_trace_without_the_program(name):
    from bench.lib.harness import Context, metric_reader

    ctx = Context(variant="qps", counters={}, groups=[("jit", 64, 9)], spans={},
                  num_classes=4, trace={"programs": {"jit_other": 1e-3}},
                  peaks=peaks("TPU v5 lite"))
    with pytest.raises(LookupError):
        metric_reader(name)(ctx)


@pytest.mark.parametrize("thetas", [[10525], [10525, 16000, 28000], [8421] * 40])
def test_planner_bytes_depends_on_the_unpadded_groups_only(thetas):
    from repro.core.mc import bucket_size

    G, L = len(thetas), 12
    want = sum(t * L * 4 for t in thetas) + G * (L * 16 + L * 4 + 24)
    assert work.planner_bytes(G, thetas, L, 4) == want
    assert work.planner_bytes(G, thetas, L, 77) == want
    # the program's buckets (groups to a multiple of 8, samples to a power
    # of two) would count more than the groups hold
    Gp, Tp = bucket_size(G, 8), bucket_size(max(thetas), 256)
    assert work.planner_bytes(Gp, [Tp] * Gp, L, 4) > want
    with pytest.raises(ValueError):
        work.planner_bytes(G + 1, thetas, L, 4)


def _drift_ctx(programs):
    from bench.lib.harness import Context

    return Context(
        variant="drift", counters={"requests": 40.0, "batches": 4.0},
        groups=[("reference", 10, 3)] * 4,
        spans={"bench.fold": np.array([[0.0, 0.001], [1.0, 1.003]]),
               "bench.replan": np.array([[2.0, 2.05], [5.0, 5.03]]),
               "bench.route": np.array([[0.0, 0.002]]),
               "bench.step": np.array([[0.0, 0.002]])},
        num_classes=4, trace={"programs": programs, "idle_pct": 99.5},
        peaks=peaks("TPU v5 lite"), num_arms=12, replans=[10, 5],
        planner=[np.full(10, 16000), np.full(5, 20000)])


def test_feedback_and_planner_readers_read_a_synthetic_context():
    from bench.lib.harness import metric_reader

    ctx = _drift_ctx({"jit__sur_greedy_scan(3)": 2e-3, "jit__wave_scan_packed": 1.0})
    read = {n: metric_reader(n + ".drift")(ctx) for n in (
        "fold_host_ms", "replan_ms", "planner_device_us", "planner_roofline",
        "rows_per_group", "route_host_ms", "device_idle_pct")}
    assert read["fold_host_ms"] == pytest.approx(1e3 * 0.004 / 4)
    assert read["replan_ms"] == pytest.approx(1e3 * 0.08 / 2)
    assert read["planner_device_us"] == pytest.approx(1e6 * 2e-3 / 2)
    moved = work.planner_bytes(10, [16000] * 10, 12, 4) + work.planner_bytes(
        5, [20000] * 5, 12, 4)
    assert read["planner_roofline"] == pytest.approx(100.0 * moved / 819e9 / 2e-3)
    assert read["rows_per_group"] == 10.0
    assert read["route_host_ms"] == pytest.approx(1e3 * 0.004 / 4)
    assert read["device_idle_pct"] == 99.5
    # no planner call and no fold in the window: nothing to read
    ctx.planner, ctx.spans = [], {}
    for n in ("fold_host_ms", "replan_ms", "planner_device_us", "planner_roofline"):
        assert metric_reader(n + ".drift")(ctx) is None


@pytest.mark.parametrize("name", ["planner_roofline.drift", "planner_device_us.drift"])
def test_planner_readers_refuse_a_trace_without_the_planner(name):
    from bench.lib.harness import metric_reader

    ctx = _drift_ctx({"jit__wave_scan_packed(1)": 1e-3})
    with pytest.raises(LookupError):
        metric_reader(name)(ctx)
