"""R-replica serving plane (repro/serving/replica.py).

The contracts pinned here, in order:

* **R=1 bit-identity.** ``ReplicaSet(router, replicas=1)`` on a stream with
  feedback (probes on), a multi-tenant cost ledger and mid-stream label
  folds produces byte-for-byte the BatchScheduler outputs: predictions,
  costs, stop waves, modes, request ids, arm totals, and every stats
  counter the baseline exposes.
* **Batch-composition invariance at R>1.** On a fault-free deterministic
  pool, fusing several workers' same-budget groups into one wave program
  (the single-device dispatch mode) cannot change any per-request output —
  fused R=4 and pump-driven heterogeneous R=2 streams bit-match a single
  baseline scheduler per request.
* **Shard-merged feedback.** Labels recorded through the replica plane and
  folded via export_shard -> merge_counts -> one central apply leave the
  estimator in exactly the single-log state (p_hat, arm counts, versions,
  drift set).
* **Fault plane at R>1.** Under an active FaultPolicy the set still
  completes, the ledger invariant ``spent + reserved <= limit`` holds per
  tenant, and the failure evidence reaches the degradation counters.
* **Compile budgets.** After ``prewarm_compile`` a replica stream causes
  zero new wave-program compiles (CompileSentinel), per replica and fused.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.clustering import kmeans
from repro.core.estimation import SuccessProbEstimator
from repro.data import OracleWorkload
from repro.serving import (
    BatchScheduler,
    CostLedger,
    FaultPolicy,
    FeedbackLog,
    PoolEngine,
    ReplicaSet,
    Request,
    ThriftRouter,
)


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


def _make_pool(K=4, L=8, clusters=5, B=96, seed=3):
    """A deterministic tabular pool; rebuilding with the same seed gives a
    bit-identical twin (the baseline side of every equivalence test)."""
    wl = OracleWorkload(num_classes=K, num_clusters=clusters, num_arms=L,
                       seed=seed)
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
        [TabularArm(f"t{a}", float(wl.costs[a]), R[a]) for a in range(L)]
    )
    router = ThriftRouter(engine, est, num_classes=K)
    return engine, router, qemb, qlab


def _budget(engine, q=0.8, mult=3.0):
    return float(np.quantile(engine.costs, q) * mult)


def _assert_block_equal(a, b):
    np.testing.assert_array_equal(a.predictions, b.predictions)
    np.testing.assert_array_equal(a.costs, b.costs)
    np.testing.assert_array_equal(a.stop_waves, b.stop_waves)
    np.testing.assert_array_equal(a.modes, b.modes)
    np.testing.assert_array_equal(a.request_ids, b.request_ids)
    np.testing.assert_array_equal(a.clusters, b.clusters)
    np.testing.assert_array_equal(a.planned_costs, b.planned_costs)


# ---------------------------------------------------------------------------
# R=1 equivalence: the whole contract, including control-plane counters
# ---------------------------------------------------------------------------


def test_r1_bit_identical_to_batch_scheduler():
    """ReplicaSet(replicas=1) IS a BatchScheduler: same outputs, same
    feedback folds (probe rng stream included), same ledger settlement,
    same stats counters on a 3-block multi-tenant stream with mid-stream
    label folds."""
    engine_a, router_a, qemb, qlab = _make_pool()
    engine_b, router_b, _, _ = _make_pool()
    budget = _budget(engine_a)
    B = qemb.shape[0]
    tenants = np.asarray(["acme", "zen", "acme"], object)

    def led():
        ledger = CostLedger(num_arms=len(engine_a.arms))
        ledger.set_limit("acme", budget * B)       # roomy: admits everything
        ledger.set_limit("zen", budget * B)
        return ledger

    rset = ReplicaSet(
        router_a, replicas=1, max_batch=16, max_wait_s=0.0,
        feedback=FeedbackLog(router_a.estimator, probe_rate=0.2, probe_seed=5),
        ledger=led(),
    )
    base = BatchScheduler(
        router_b, max_batch=16, max_wait_s=0.0,
        feedback=FeedbackLog(router_b.estimator, probe_rate=0.2, probe_seed=5),
        ledger=led(),
    )
    assert rset.fuse_waves is False                # never fuses at R=1

    cuts = [(0, 32), (32, 64), (64, B)]
    for sched in (rset, base):
        for k, (s, e) in enumerate(cuts):
            blk = sched.submit_many(
                np.arange(s, e), qemb[s:e], budget, tenant=tenants[k]
            )
            sched.drain()
            sched.record_outcomes(blk.request_ids, qlab[s:e])
            if k < len(cuts) - 1:
                continue
            sched.apply_feedback()                 # fold the tail too

    # rebuild both streams' blocks through one more pass for comparison
    rset_blocks, base_blocks = [], []
    for sched, out in ((rset, rset_blocks), (base, base_blocks)):
        for s, e in cuts:
            out.append(sched.submit_many(np.arange(s, e), qemb[s:e], budget))
        sched.drain()
    for a, b in zip(rset_blocks, base_blocks):
        _assert_block_equal(a, b)

    np.testing.assert_array_equal(rset.arm_query_totals, base.arm_query_totals)
    rstats = rset.stats
    for k, v in base.stats.items():                # rset adds replica_* keys
        if k == "queue_wait_s":                    # a clock reading, not a count
            assert rstats[k] >= 0.0 and v >= 0.0
            continue
        assert rstats[k] == v, f"stats[{k}]: replica {rstats[k]} != base {v}"
    assert rstats["replicas"] == 1
    assert rstats["replica_fused"] == 0 and rstats["replica_spills"] == 0
    lat = rset.latency_stats()
    assert lat["count"] == base.latency_stats()["count"]


def test_r1_submit_single_requests_match():
    engine_a, router_a, qemb, _ = _make_pool(B=48)
    engine_b, router_b, _, _ = _make_pool(B=48)
    budget = _budget(engine_a)
    rset = ReplicaSet(router_a, replicas=1, max_batch=16, max_wait_s=0.0)
    base = BatchScheduler(router_b, max_batch=16, max_wait_s=0.0)
    fa = [rset.submit(Request(payload=j, embedding=qemb[j], budget=budget))
          for j in range(48)]
    fb = [base.submit(Request(payload=j, embedding=qemb[j], budget=budget))
          for j in range(48)]
    rset.drain()
    base.drain()
    for x, y in zip(fa, fb):
        rx, ry = x.result(), y.result()
        assert (rx.prediction, rx.cost, rx.stop_wave, rx.mode) == \
               (ry.prediction, ry.cost, ry.stop_wave, ry.mode)


# ---------------------------------------------------------------------------
# R>1: fused / sharded dispatch is batch-composition invariant per request
# ---------------------------------------------------------------------------


def test_r4_fused_matches_baseline_per_request():
    """On a fault-free deterministic pool, per-query routing does not
    depend on which rows share a wave program: the fused R=4 outputs equal
    a single baseline scheduler's, row for row."""
    engine_a, router_a, qemb, _ = _make_pool()
    engine_b, router_b, _, _ = _make_pool()
    budget = _budget(engine_a)
    B = qemb.shape[0]

    rset = ReplicaSet(router_a, replicas=4, max_batch=16, max_wait_s=0.0)
    assert rset.fuse_waves is True or len(__import__("jax").devices()) > 1
    blk = rset.submit_many(np.arange(B), qemb, budget)
    rset.drain()

    base = BatchScheduler(router_b, max_batch=B, max_wait_s=0.0)
    ref = base.submit_many(np.arange(B), qemb, budget)
    base.drain()

    np.testing.assert_array_equal(blk.predictions, ref.predictions)
    np.testing.assert_array_equal(blk.costs, ref.costs)
    np.testing.assert_array_equal(blk.stop_waves, ref.stop_waves)
    np.testing.assert_array_equal(rset.arm_query_totals, base.arm_query_totals)
    st = rset.stats
    assert st["completed"] == B
    if rset.fuse_waves:
        assert st["replica_fused"] >= 1           # fusion actually engaged
        assert st["replica_fused_rows"] <= B


def test_r2_hetero_budgets_pump_driven_matches():
    """Heterogeneous budgets, driven by pump() like a live front door:
    every request still gets its composition-invariant result, across
    budget-group splits, affinity shards and fusions."""
    engine_a, router_a, qemb, _ = _make_pool()
    engine_b, router_b, _, _ = _make_pool()
    B = qemb.shape[0]
    rng = np.random.default_rng(11)
    levels = np.quantile(engine_a.costs, [0.4, 0.8]) * 2.5
    budgets = rng.choice(levels, size=B)

    rset = ReplicaSet(router_a, replicas=2, max_batch=8, max_wait_s=0.0)
    blocks = []
    for s in range(0, B, 24):
        blocks.append(rset.submit_many(
            np.arange(s, min(s + 24, B)), qemb[s:s + 24], budgets[s:s + 24]
        ))
        rset.pump()
    rset.drain()
    assert all(b.done() for b in blocks)

    base = BatchScheduler(router_b, max_batch=B, max_wait_s=0.0)
    ref = base.submit_many(np.arange(B), qemb, budgets)
    base.drain()
    got_p = np.concatenate([b.predictions for b in blocks])
    got_c = np.concatenate([b.costs for b in blocks])
    np.testing.assert_array_equal(got_p, ref.predictions)
    np.testing.assert_array_equal(got_c, ref.costs)


def test_affinity_is_sticky_and_spill_caps_skew():
    """The same embedding always lands on the same replica; a block whose
    clusters all hash to one replica spills its tail to the least loaded."""
    engine, router, qemb, _ = _make_pool()
    budget = _budget(engine)
    rset = ReplicaSet(router, replicas=4, max_batch=16, max_wait_s=0.0)
    a1 = rset._assign(qemb, qemb.shape[0])
    a2 = rset._assign(qemb, qemb.shape[0])
    np.testing.assert_array_equal(a1, a2)          # stateless affinity
    # all rows from ONE cluster: affinity alone would pile them on one
    # replica; the home keeps its FIFO prefix up to the cap and the tail
    # spills to the least-loaded replica
    one = np.repeat(qemb[:1], 64, axis=0)
    home = int(rset._assign(one[:1], 1)[0])
    before = rset.spills
    assign = rset._assign(one, 64)
    cap = int(np.ceil(rset.spill_factor * 64 / 4))
    counts = np.bincount(assign, minlength=4)
    assert counts[home] == cap                     # prefix stays home
    assert rset.spills - before == 64 - cap        # tail spilled elsewhere
    assert (counts > 0).sum() >= 2
    blk = rset.submit_many(np.arange(64) % qemb.shape[0], one, budget)
    rset.drain()
    assert blk.done() and (blk.predictions >= 0).all()


def test_spill_multi_overflow_no_double_count_never_self_spill():
    """Regression: when SEVERAL replicas overflow in one block, each sheds
    exactly its own tail once — the spill counter equals the true excess
    (it used to double-count rows that landed on another over-cap home and
    were then re-spilled), every over-cap home ends exactly at cap, and no
    spilled row lands back on its own home."""
    engine, router, qemb, _ = _make_pool()
    rset = ReplicaSet(router, replicas=4, max_batch=16, max_wait_s=0.0,
                      spill_factor=1.0)
    # two embeddings with DISTINCT affinity homes, 32 rows each: both
    # homes overflow the cap = ceil(1.0 * 64 / 4) = 16 simultaneously
    homes = {int(rset._assign(qemb[i:i + 1], 1)[0]): i
             for i in range(qemb.shape[0])}
    (h1, i1), (h2, i2) = list(homes.items())[:2]
    assert h1 != h2
    emb = np.concatenate([np.repeat(qemb[i1:i1 + 1], 32, axis=0),
                          np.repeat(qemb[i2:i2 + 1], 32, axis=0)])
    before = rset.spills
    assign = rset._assign(emb, 64)
    cap = int(np.ceil(rset.spill_factor * 64 / 4))
    counts = np.bincount(assign, minlength=4)
    assert counts[h1] == cap and counts[h2] == cap   # prefixes stay home
    assert rset.spills - before == 64 - 2 * cap      # counted once each
    # the shed tails went to the two idle replicas, not each other's home
    tails = np.concatenate([assign[:32][assign[:32] != h1],
                            assign[32:][assign[32:] != h2]])
    assert not np.isin(tails, [h1, h2]).any()
    assert counts.sum() == 64


# ---------------------------------------------------------------------------
# Shard-merged feedback: replica-plane folds == single-log folds
# ---------------------------------------------------------------------------


def test_shard_merge_reproduces_single_log_estimator_state():
    """Labels stream through an R=3 replica plane (three local shard logs,
    merged at ONE central apply) vs the same labels through a single
    BatchScheduler log: the estimator ends bit-identical — p_hat, arm
    counts, per-cluster versions, global version."""
    engine_a, router_a, qemb, qlab = _make_pool()
    engine_b, router_b, _, _ = _make_pool()
    budget = _budget(engine_a)
    B = qemb.shape[0]

    rset = ReplicaSet(router_a, replicas=3, max_batch=16, max_wait_s=0.0,
                      feedback=True)
    blk = rset.submit_many(np.arange(B), qemb, budget)
    rset.drain()
    assert rset.record_outcomes(blk.request_ids, qlab) == B
    rep_r = rset.apply_feedback()

    base = BatchScheduler(router_b, max_batch=16, max_wait_s=0.0,
                          feedback=True)
    ref = base.submit_many(np.arange(B), qemb, budget)
    base.drain()
    base.record_outcomes(ref.request_ids, qlab)
    rep_b = base.apply_feedback()

    assert rep_r.labels == rep_b.labels == B
    assert sorted(rep_r.clusters) == sorted(rep_b.clusters)
    assert sorted(rep_r.drifted) == sorted(rep_b.drifted)
    est_r, est_b = router_a.estimator, router_b.estimator
    assert est_r.version == est_b.version
    assert est_r.plan_version == est_b.plan_version
    assert set(est_r.clusters) == set(est_b.clusters)
    for cid, st in est_r.clusters.items():
        st2 = est_b.clusters[cid]
        np.testing.assert_array_equal(st.p_hat, st2.p_hat)
        np.testing.assert_array_equal(st.arm_counts, st2.arm_counts)
        assert st.version == st2.version
    fr, fb = rset.stats, base.stats
    for k in ("feedback_labels", "feedback_applies", "feedback_drifts",
              "feedback_unmatched"):
        assert fr[k] == fb[k], k


def test_stray_labels_land_on_central_log():
    engine, router, qemb, qlab = _make_pool(B=32)
    rset = ReplicaSet(router, replicas=2, max_batch=16, max_wait_s=0.0,
                      feedback=True)
    blk = rset.submit_many(np.arange(32), qemb, _budget(engine))
    rset.drain()
    matched = rset.record_outcomes(
        np.concatenate([blk.request_ids, [10 ** 9]]),
        np.concatenate([qlab[:32], [0]]),
    )
    assert matched == 32
    assert rset.stats["feedback_unmatched"] == 1


# ---------------------------------------------------------------------------
# Fault plane + ledger threading at R>1
# ---------------------------------------------------------------------------


def test_replica_faults_complete_with_ledger_invariant():
    """Fused dispatch changes fault-draw row indices (documented caveat),
    so R>1 under faults pins behavioral invariants, not bit-identity: the
    stream completes, failure evidence reaches the degradation counters,
    and every tenant holds ``spent + reserved <= limit``."""
    engine, router, qemb, qlab = _make_pool()
    budget = _budget(engine)
    B = qemb.shape[0]
    ledger = CostLedger(num_arms=len(engine.arms))
    ledger.set_limit("acme", budget * B)
    policy = FaultPolicy(len(engine.arms), 4, seed=7)
    hot = int(np.argmin(engine.costs))
    policy.set_arm(hot, timeout=0.4, error=0.3)
    engine.fault_policy = policy
    try:
        rset = ReplicaSet(router, replicas=3, max_batch=16, max_wait_s=0.0,
                          feedback=True, ledger=ledger)
        blk = rset.submit_many(np.arange(B), qemb, budget, tenant="acme")
        rset.drain()
        assert blk.done() and (blk.predictions >= 0).all()
        rset.record_outcomes(blk.request_ids, qlab)
        rset.apply_feedback()
        st = rset.stats
        assert st["degradation_failures"] > 0      # evidence was threaded
        assert st["degradation_routes"] > 0
        ent = ledger.tenant("acme")
        assert ent["spent"] + ent["reserved"] <= ent["limit"] + 1e-9
        assert ent["reserved"] == 0.0              # fully settled at drain
        assert np.isclose(ent["spent"], blk.costs.sum())
    finally:
        engine.fault_policy = None


def test_replica_tenant_budget_rejections_match_baseline():
    """A tenant that runs out of budget mid-stream is rejected identically
    through the replica plane: prediction -1, cost 0, mode 'rejected',
    and the ledger never over-commits."""
    engine_a, router_a, qemb, _ = _make_pool()
    engine_b, router_b, _, _ = _make_pool()
    budget = _budget(engine_a)
    B = qemb.shape[0]
    cap = budget * (B // 4)                        # fits ~a quarter

    def run(sched_cls, router):
        ledger = CostLedger(num_arms=len(engine_a.arms))
        ledger.set_limit("acme", cap)
        if sched_cls is ReplicaSet:
            s = ReplicaSet(router, replicas=1, max_batch=16, max_wait_s=0.0,
                           ledger=ledger)
        else:
            s = BatchScheduler(router, max_batch=16, max_wait_s=0.0,
                               ledger=ledger)
        blk = s.submit_many(np.arange(B), qemb, budget, tenant="acme")
        s.drain()
        return blk, ledger

    blk_r, led_r = run(ReplicaSet, router_a)
    blk_b, led_b = run(BatchScheduler, router_b)
    _assert_block_equal(blk_r, blk_b)
    rej = blk_r.modes == "rejected"
    assert rej.any()
    assert (blk_r.predictions[rej] == -1).all()
    assert (blk_r.costs[rej] == 0).all()
    assert led_r.tenant("acme")["spent"] == led_b.tenant("acme")["spent"]
    assert led_r.tenant("acme")["spent"] <= cap


# ---------------------------------------------------------------------------
# Compile budgets: zero timed recompiles per replica
# ---------------------------------------------------------------------------


def test_replica_stream_zero_recompiles_after_prewarm():
    """prewarm_compile covers both the per-worker admission bucket and the
    fused concatenation bucket; a full R=4 stream (fused dispatches
    included) then never compiles a new wave program."""
    from repro.analysis import CompileSentinel
    from repro.serving import router as router_mod

    engine, router, qemb, _ = _make_pool()
    budget = _budget(engine)
    rset = ReplicaSet(router, replicas=4, max_batch=16, max_wait_s=0.0)
    rset.prewarm(budgets=[budget])
    rset.prewarm_compile()
    sentinel = CompileSentinel({"wave": router_mod._wave_scan})
    sentinel.snapshot()
    for _ in range(3):
        blk = rset.submit_many(np.arange(qemb.shape[0]), qemb, budget)
        rset.drain()
        assert blk.done()
    sentinel.assert_no_new_compiles(
        detail="R=4 replica stream after prewarm_compile"
    )
