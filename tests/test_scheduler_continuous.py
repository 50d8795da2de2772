"""Continuous-batching front-end: equivalence with one-shot routing, the
cost-aware speculation switch, wave-stepped future completion, SLO-aware
admission, and stats consistency under interleaved submits.

Determinism comes from tabular arms (as in test_router_batched): each arm's
response to query j is precomputed, so admission order, budget grouping and
speculative gathering cannot change what any arm answers — continuous-mode
results must therefore be *exactly* the one-shot ``route_batch`` results on
the same request stream.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.clustering import kmeans
from repro.core.estimation import SuccessProbEstimator
from repro.data import OracleWorkload
from repro.serving import BatchScheduler, PoolEngine, Request, ThriftRouter


@dataclasses.dataclass
class TabularArm:
    """Deterministic arm: response to query j is the precomputed resp[j]."""

    name: str
    cost: float
    resp: np.ndarray
    metered: bool = False

    def classify_batch(self, queries) -> np.ndarray:
        return self.resp[np.asarray(queries, np.int64)]

    def latency_s(self, batch: int) -> float:
        return 1e-6 * self.cost * batch


def _make_pool(K=4, L=8, clusters=5, B=96, seed=3, metered=False):
    wl = OracleWorkload(num_classes=K, num_clusters=clusters, num_arms=L, seed=seed)
    T, emb, _ = wl.response_table(60 * clusters, seed=seed + 1)
    assign, _ = kmeans(emb, clusters, seed=0)
    est = SuccessProbEstimator(T, emb, assign)
    rng = np.random.default_rng(seed + 2)
    qcid, qemb, qlab = wl.sample_queries(B, rng)
    R = np.stack(
        [
            wl.invoke_batch(a, qcid, qlab, np.random.default_rng(seed + 100 + a))
            for a in range(L)
        ]
    )
    engine = PoolEngine(
        [TabularArm(f"t{a}", float(wl.costs[a]), R[a], metered=metered)
         for a in range(L)]
    )
    router = ThriftRouter(engine, est, num_classes=K)
    return engine, router, qemb


def _oneshot_stream(router, qemb, budgets, max_batch):
    """The one-shot equivalent of the continuous pipeline: FIFO admission
    chunks of ``max_batch``, split into budget groups in first-occurrence
    order, each group routed by a plain ``route_batch`` call."""
    B = budgets.shape[0]
    preds = np.zeros(B, np.int64)
    costs = np.zeros(B, np.float64)
    stop_waves = np.zeros(B, np.int64)
    for s in range(0, B, max_batch):
        rows = np.arange(s, min(s + max_batch, B))
        chunk_budgets = budgets[rows]
        if (chunk_budgets == chunk_budgets[0]).all():
            groups = [rows]
        else:
            _, first = np.unique(chunk_budgets, return_index=True)
            groups = [
                rows[chunk_budgets == chunk_budgets[i]] for i in np.sort(first)
            ]
        for g in groups:
            res = router.route_batch(g, qemb[g], budgets[g])
            preds[g] = res.predictions
            costs[g] = res.costs
            stop_waves[g] = res.stop_waves
    return preds, costs, stop_waves


@pytest.mark.parametrize("hetero", [False, True])
def test_continuous_matches_oneshot_stream(hetero):
    engine, router, qemb = _make_pool(B=96)
    B = qemb.shape[0]
    rng = np.random.default_rng(11)
    levels = np.quantile(engine.costs, [0.4, 0.8]) * 2.5
    budgets = (
        rng.choice(levels, size=B) if hetero
        else np.full(B, float(levels[1]))
    )

    sched = BatchScheduler(router, max_batch=32, max_wait_s=0.0)
    futs = [
        sched.submit(Request(payload=j, embedding=qemb[j], budget=budgets[j]))
        for j in range(B)
    ]
    sched.drain()

    # a second identical pool routed one-shot must reproduce every output
    _, router2, _ = _make_pool(B=96)
    preds, costs, stop_waves = _oneshot_stream(router2, qemb, budgets, 32)

    assert all(f.done() for f in futs)
    results = [f.result() for f in futs]
    np.testing.assert_array_equal([r.prediction for r in results], preds)
    np.testing.assert_allclose(
        [r.cost for r in results], costs, rtol=1e-12, atol=0
    )
    np.testing.assert_array_equal([r.stop_wave for r in results], stop_waves)
    assert all(r.mode == "jit" for r in results)  # unmetered pool speculates


def test_saturation_coalescing_matches_oneshot_and_caps_admission():
    """coalesce > 1: a saturated backlog is admitted in up-to
    ``coalesce * max_batch`` chunks; results still exactly match the
    one-shot stream at that effective chunking, and flush() never grows."""
    engine, router, qemb = _make_pool(B=96)
    _, router2, _ = _make_pool(B=96)
    budget = float(np.quantile(engine.costs, 0.6)) * 2

    sched = BatchScheduler(router, max_batch=16, max_wait_s=0.0, coalesce=3)
    blk = sched.submit_many(np.arange(96), qemb, budget)
    sched.drain()
    # backlog of 96 > 16 -> admissions of 48: two flushes, not six
    assert sched.stats["flushes"] == 2

    preds, costs, _ = _oneshot_stream(
        router2, qemb, np.full(96, budget), 48
    )
    np.testing.assert_array_equal(blk.predictions, preds)
    np.testing.assert_allclose(blk.costs, costs, rtol=1e-12, atol=0)

    # the legacy one-shot flush() API never coalesces
    sched2 = BatchScheduler(router, max_batch=16, max_wait_s=0.0, coalesce=3)
    sched2.submit_many(np.arange(96), qemb, budget)
    (batch, res) = sched2.flush()[0]
    assert len(batch) == 16 and res.predictions.shape[0] == 16


def test_block_submission_matches_single_submits():
    engine, router, qemb = _make_pool(B=64)
    _, router2, _ = _make_pool(B=64)
    budget = float(np.quantile(engine.costs, 0.6)) * 2

    sched1 = BatchScheduler(router, max_batch=16, max_wait_s=0.0)
    futs = [
        sched1.submit(Request(payload=j, embedding=qemb[j], budget=budget))
        for j in range(64)
    ]
    sched1.drain()

    sched2 = BatchScheduler(router2, max_batch=16, max_wait_s=0.0)
    blk = sched2.submit_many(np.arange(64), qemb, budget)
    sched2.drain()

    np.testing.assert_array_equal(
        blk.predictions, [f.result().prediction for f in futs]
    )
    np.testing.assert_allclose(
        blk.costs, [f.result().cost for f in futs], rtol=1e-12, atol=0
    )
    np.testing.assert_array_equal(
        blk.stop_waves, [f.result().stop_wave for f in futs]
    )
    assert blk.done() and blk.result() is blk


def test_speculation_switch_metered_vs_oracle():
    """auto mode: cheap unmetered pool -> speculative jit plane; metered
    pool -> compacting reference plane; identical predictions either way."""
    _, router_free, qemb = _make_pool(B=48, metered=False)
    engine_m, router_m, _ = _make_pool(B=48, metered=True)
    budget = float(np.quantile(engine_m.costs, 0.6)) * 2

    s_free = BatchScheduler(router_free, max_batch=16, max_wait_s=0.0)
    blk_free = s_free.submit_many(np.arange(48), qemb, budget)
    s_free.drain()
    assert set(blk_free.modes.tolist()) == {"jit"}
    assert s_free.stats["spec_jit"] == 3 and s_free.stats["spec_reference"] == 0

    s_met = BatchScheduler(router_m, max_batch=16, max_wait_s=0.0)
    blk_met = s_met.submit_many(np.arange(48), qemb, budget)
    s_met.drain()
    assert set(blk_met.modes.tolist()) == {"reference"}
    assert s_met.stats["spec_reference"] == 3 and s_met.stats["spec_jit"] == 0

    # the data plane never changes the answers
    np.testing.assert_array_equal(blk_free.predictions, blk_met.predictions)
    np.testing.assert_allclose(blk_free.costs, blk_met.costs, rtol=1e-12, atol=0)

    # a budget-sized threshold lets the switch speculate on a metered pool:
    # the worst-case speculative exposure per query can never exceed the
    # planned (in-budget) spend, so budget-per-query is always enough
    s_thresh = BatchScheduler(
        router_m, max_batch=16, max_wait_s=0.0, speculation_threshold=budget
    )
    blk_thresh = s_thresh.submit_many(np.arange(48), qemb, budget)
    s_thresh.drain()
    assert set(blk_thresh.modes.tolist()) == {"jit"}

    # and the plane can be pinned outright
    s_pin = BatchScheduler(router_m, max_batch=16, max_wait_s=0.0,
                           speculation="jit")
    blk_pin = s_pin.submit_many(np.arange(48), qemb, budget)
    s_pin.drain()
    assert set(blk_pin.modes.tolist()) == {"jit"}


def test_speculation_cost_metadata():
    engine_free, router_free, qemb = _make_pool(B=16, metered=False)
    engine_m, router_m, _ = _make_pool(B=16, metered=True)
    budget = float(np.quantile(engine_free.costs, 0.6)) * 2
    assert not engine_free.any_metered and engine_m.any_metered
    p_free = router_free.begin_route(np.arange(16), qemb, budget, mode="auto")
    p_met = router_m.begin_route(np.arange(16), qemb, budget, mode="auto")
    assert p_free.kind == "jit" and p_free.spec_cost == 0.0
    assert p_met.kind == "reference" and p_met.spec_cost > 0.0
    # exposure is the full scheduled metered spend per query
    assert p_met.spec_cost <= budget + 1e-12
    p_free.result(), p_met.result()


def test_reference_wave_stepping_resolves_at_stop_wave():
    """PendingRoute.step(): queries complete in stop-wave order with their
    final predictions, matching the one-shot reference result exactly."""
    engine, router, qemb = _make_pool(B=64)
    _, router2, _ = _make_pool(B=64)
    budget = float(engine.costs.sum())     # everything affordable: deep plans
    res = router2.route_batch_reference(np.arange(64), qemb, budget)

    pending = router.begin_route(np.arange(64), qemb, budget, mode="reference")
    seen = np.full(64, -1, np.int64)
    preds = np.full(64, -1, np.int64)
    wave = 0
    while not pending.exhausted:
        rows, p = pending.step()
        assert np.all(seen[rows] == -1), "a query completed twice"
        seen[rows] = min(wave, pending.T)
        preds[rows] = p
        wave += 1
    assert (seen >= 0).all(), "every query completes through step()"
    np.testing.assert_array_equal(seen, res.stop_waves)
    np.testing.assert_array_equal(preds, res.predictions)
    # finalization after stepping reproduces the one-shot result
    out = pending.result()
    np.testing.assert_array_equal(out.predictions, res.predictions)
    np.testing.assert_allclose(out.costs, res.costs, rtol=1e-12, atol=0)
    np.testing.assert_array_equal(out.invoked, res.invoked)


def test_stats_consistent_under_interleaved_submits():
    engine, router, qemb = _make_pool(B=96)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    sched = BatchScheduler(router, max_batch=24, max_wait_s=0.0)

    futs = [
        sched.submit(Request(payload=j, embedding=qemb[j], budget=budget))
        for j in range(20)
    ]
    sched.pump()
    blk = sched.submit_many(np.arange(20, 70), qemb[20:70], budget)
    sched.pump()
    futs += [
        sched.submit(Request(payload=j, embedding=qemb[j], budget=budget))
        for j in range(70, 96)
    ]
    sched.drain()

    st = sched.stats
    assert st["submitted"] == 96
    assert st["requests"] == 96            # everything admitted
    assert st["completed"] == 96
    assert all(f.done() for f in futs) and blk.done()
    assert st["batches"] >= st["flushes"] >= 96 // 24
    # admission - arrival: never negative, never past completion
    latency_sum = sum(f.result().latency_s for f in futs) + float(
        blk.latencies_s.sum()
    )
    assert 0.0 <= st["queue_wait_s"] <= latency_sum
    assert st["spec_jit"] + st["spec_reference"] == st["batches"]
    # one mitigator record per routed group
    assert len(sched.mitigator.history) == min(st["batches"],
                                               sched.mitigator.window)
    # per-arm accounting: every invoked wave is one arm-query
    total_waves = sum(f.result().stop_wave for f in futs) + int(
        blk.stop_waves.sum()
    )
    assert int(sched.arm_query_totals.sum()) == total_waves
    # plan-cache counters mirrored and self-consistent
    assert st["plan_hits"] + st["plan_misses"] >= st["batches"]
    assert sched.latency_stats()["count"] == 96
    assert sched.latency_stats()["p99_s"] >= sched.latency_stats()["p50_s"]


def test_empty_block_and_pinned_router_under_auto():
    engine, router, qemb = _make_pool(B=16)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    sched = BatchScheduler(router, max_batch=8, max_wait_s=0.0)
    # a zero-length burst is a no-op, not a poisoned queue
    empty = sched.submit_many(np.zeros((0, 2), np.int64), np.zeros((0, 4)),
                              budget)
    assert empty.done() and empty.n == 0
    assert not sched.ready() and sched.drain() == 0
    blk = sched.submit_many(np.arange(16), qemb, budget)
    sched.drain()
    assert blk.done()

    # a router pinned to the reference plane (jit_waves=False) keeps it
    # under mode="auto" even though no arm carries a metered flag
    from repro.serving import ThriftRouter as TR
    router_pinned = TR(engine, router.estimator, num_classes=4,
                       jit_waves=False)
    pending = router_pinned.begin_route(np.arange(16), qemb, budget,
                                        mode="auto")
    assert pending.kind == "reference"
    pending.result()


def test_slo_tightens_admission_deadline():
    engine, router, qemb = _make_pool(B=8)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    sched = BatchScheduler(router, max_batch=64, max_wait_s=60.0,
                           slo_margin_s=0.0)
    sched.submit(Request(payload=0, embedding=qemb[0], budget=budget))
    assert not sched.ready()               # long max_wait, batch not full
    deadline_no_slo = sched.next_deadline()
    sched.submit(Request(payload=1, embedding=qemb[1], budget=budget,
                         slo_s=0.0))
    assert sched.next_deadline() < deadline_no_slo
    assert sched.ready()                   # SLO already due -> flush now
    assert sched.drain() == 2


def test_queue_composition_prefetch():
    engine, router, qemb = _make_pool(B=32)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    sched = BatchScheduler(router, max_batch=64, max_wait_s=60.0)
    for j in range(32):
        sched.submit(Request(payload=j, embedding=qemb[j], budget=budget))
    assert not sched.ready()
    sched.pump()                           # idle time -> plan prefetch
    st_mid = dict(router.plans.stats())
    assert st_mid["plan_prefetches"] > 0
    misses_before = st_mid["plan_misses"]
    sched.drain()
    assert router.plans.stats()["plan_misses"] == misses_before
    assert sched.stats["completed"] == 32


def test_flush_api_unchanged_and_resolves_futures():
    engine, router, qemb = _make_pool(B=32)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    sched = BatchScheduler(router, max_batch=16, max_wait_s=0.0)
    futs = [
        sched.submit(Request(payload=j, embedding=qemb[j], budget=budget))
        for j in range(32)
    ]
    out = sched.flush()
    assert len(out) == 1
    batch, res = out[0]
    assert len(batch) == 16 and all(isinstance(r, Request) for r in batch)
    assert all(f.done() for f in futs[:16])
    assert not any(f.done() for f in futs[16:])
    np.testing.assert_array_equal(
        [f.result().prediction for f in futs[:16]], res.predictions
    )
    sched.drain()
    assert all(f.done() for f in futs)
