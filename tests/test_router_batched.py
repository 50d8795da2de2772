"""Batched wavefront router (jitted + compacting) vs per-query references.

Three implementations must agree exactly on deterministic pools:
``route_batch`` (the on-device jitted scan), ``route_batch_reference`` (the
compacting host wavefront) and a loop calling ``adaptive_invoke`` once per
query — identical predictions, per-query costs and arms-used, across
heterogeneous (K, budget, cluster) mixes. Determinism comes from tabular
arms: each arm's response to query j is precomputed, so invocation order,
batching and speculative response gathering cannot change what any arm
answers.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.belief import tie_break_argmax
from repro.core.clustering import kmeans
from repro.core.estimation import SuccessProbEstimator
from repro.core.selection import adaptive_invoke
from repro.core.types import SelectionResult
from repro.data import OracleWorkload
from repro.serving import BatchScheduler, PoolEngine, Request, ThriftRouter


@dataclasses.dataclass
class TabularArm:
    """Deterministic arm: response to query j is the precomputed resp[j]."""

    name: str
    cost: float
    resp: np.ndarray

    def classify_batch(self, queries) -> np.ndarray:
        return self.resp[np.asarray(queries, np.int64)]

    def latency_s(self, batch: int) -> float:
        return 1e-6 * self.cost * batch


def _make_pool(K, L, clusters, B, seed):
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
        [TabularArm(f"t{a}", float(wl.costs[a]), R[a]) for a in range(L)]
    )
    router = ThriftRouter(engine, est, num_classes=K)
    return wl, est, engine, router, qemb, R


def _reference(router, est, R, qemb, budgets, K):
    """Per-query adaptive_invoke loop — the semantics the batch must match."""
    B = qemb.shape[0]
    cids = est.lookup_batch(qemb)
    preds, costs, planned, arms = [], [], [], []
    for j in range(B):
        p = est.clusters[int(cids[j])].p_hat
        sel = router.selector.select(p, K, float(budgets[j]))
        inv = adaptive_invoke(
            list(sel.chosen), p, K, lambda a: int(R[a, j]),
            costs=router.engine.costs,
        )
        preds.append(inv.prediction)
        costs.append(inv.cost)
        planned.append(inv.planned_cost)
        arms.append([int(a) for a in inv.used])
    return np.asarray(preds), np.asarray(costs), np.asarray(planned), arms


MIXES = [
    # (K, L, clusters, B, seed, budget quantiles used per query)
    (4, 8, 5, 96, 3, [0.5]),
    (2, 6, 3, 64, 7, [0.3, 0.8]),
    (5, 12, 6, 128, 11, [0.2, 0.55, 0.9]),
]


@pytest.mark.parametrize("K,L,clusters,B,seed,quantiles", MIXES)
def test_batched_matches_sequential_reference(K, L, clusters, B, seed, quantiles):
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    rng = np.random.default_rng(seed + 5)
    levels = np.quantile(engine.costs, quantiles) * 2.5
    budgets = rng.choice(levels, size=B)  # heterogeneous budgets in one batch

    res = router.route_batch(np.arange(B), qemb, budgets)
    preds, costs, planned, arms = _reference(router, est, R, qemb, budgets, K)

    np.testing.assert_array_equal(res.predictions, preds)
    np.testing.assert_allclose(res.costs, costs, rtol=1e-12, atol=0)
    np.testing.assert_allclose(res.planned_costs, planned, rtol=1e-12, atol=0)
    assert res.arms_used == arms
    # arm accounting is consistent with the per-query trace
    total = np.zeros(L, np.int64)
    for a_list in arms:
        total[a_list] += 1
    np.testing.assert_array_equal(res.arm_query_counts, total)


@pytest.mark.parametrize("K,L,clusters,B,seed,quantiles", MIXES)
def test_jitted_matches_compacting_reference(K, L, clusters, B, seed, quantiles):
    """route_batch (jitted scan) == route_batch_reference (compacting loop)
    on every output, including the invoked mask and arm accounting."""
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    rng = np.random.default_rng(seed + 5)
    levels = np.quantile(engine.costs, quantiles) * 2.5
    budgets = rng.choice(levels, size=B)
    res = router.route_batch(np.arange(B), qemb, budgets)
    ref = router.route_batch_reference(np.arange(B), qemb, budgets)
    np.testing.assert_array_equal(res.predictions, ref.predictions)
    np.testing.assert_allclose(res.costs, ref.costs, rtol=1e-12, atol=0)
    np.testing.assert_allclose(res.planned_costs, ref.planned_costs, rtol=1e-12, atol=0)
    np.testing.assert_array_equal(res.invoked, ref.invoked)
    np.testing.assert_array_equal(res.arm_query_counts, ref.arm_query_counts)
    assert res.arms_used == ref.arms_used
    assert res.waves == ref.waves


@pytest.mark.parametrize("K,L,clusters,B,seed,quantiles", MIXES[:1])
def test_reference_route_batch_agrees(K, L, clusters, B, seed, quantiles):
    """All three paths agree: jitted == compacting == per-query sequential."""
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    res = router.route_batch(np.arange(B), qemb, budget)
    ref = router.route_batch_reference(np.arange(B), qemb, budget)
    seq = router.route_batch_sequential(np.arange(B), qemb, budget)
    for other in (ref, seq):
        np.testing.assert_array_equal(res.predictions, other.predictions)
        np.testing.assert_allclose(res.costs, other.costs, rtol=1e-12, atol=0)
        assert res.arms_used == other.arms_used


def test_jit_waves_false_dispatches_to_reference():
    K, L, clusters, B, seed = 4, 8, 5, 48, 3
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    router_ref = ThriftRouter(engine, est, num_classes=K, jit_waves=False)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    res = router.route_batch(np.arange(B), qemb, budget)
    res_ref = router_ref.route_batch(np.arange(B), qemb, budget)
    np.testing.assert_array_equal(res.predictions, res_ref.predictions)
    np.testing.assert_allclose(res.costs, res_ref.costs, rtol=1e-12, atol=0)
    assert res.arms_used == res_ref.arms_used


def test_donate_buffers_off_is_bit_identical():
    """PR 10: the serving default donates the staged wave tables
    (`_wave_scan`); `donate_buffers=False` routes through the nodonate
    twin. Both must produce bitwise the same routes — donation is a
    storage contract, never a numerics knob."""
    K, L, clusters, B, seed = 4, 8, 5, 64, 3
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    assert router.donate_buffers            # serving default
    router_nd = ThriftRouter(
        engine, est, num_classes=K, donate_buffers=False
    )
    rng = np.random.default_rng(seed + 5)
    budgets = rng.choice(np.quantile(engine.costs, [0.3, 0.8]) * 2.5, size=B)
    res = router.route_batch(np.arange(B), qemb, budgets)
    res_nd = router_nd.route_batch(np.arange(B), qemb, budgets)
    np.testing.assert_array_equal(res.predictions, res_nd.predictions)
    np.testing.assert_allclose(res.costs, res_nd.costs, rtol=0, atol=0)
    np.testing.assert_allclose(
        res.planned_costs, res_nd.planned_costs, rtol=0, atol=0
    )
    assert res.arms_used == res_nd.arms_used


def test_kernel_backend_matches_on_jitted_and_reference_paths():
    """use_kernel=True: the Pallas kernel dispatched from inside the jitted
    scan agrees with the kernel-backed compacting loop and the numpy path."""
    K, L, clusters, B, seed = 5, 12, 6, 96, 11
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    router_k = ThriftRouter(engine, est, num_classes=K, use_kernel=True)
    rng = np.random.default_rng(seed + 5)
    budgets = rng.choice(np.quantile(engine.costs, [0.3, 0.8]) * 2.5, size=B)
    res_k = router_k.route_batch(np.arange(B), qemb, budgets)
    ref_k = router_k.route_batch_reference(np.arange(B), qemb, budgets)
    res = router.route_batch(np.arange(B), qemb, budgets)
    np.testing.assert_array_equal(res_k.predictions, ref_k.predictions)
    np.testing.assert_allclose(res_k.costs, ref_k.costs, rtol=1e-12, atol=0)
    assert res_k.arms_used == ref_k.arms_used
    np.testing.assert_array_equal(res_k.predictions, res.predictions)
    assert res_k.arms_used == res.arms_used


def test_kernel_backend_matches_numpy_backend():
    K, L, clusters, B, seed = 4, 8, 5, 64, 3
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    router_k = ThriftRouter(engine, est, num_classes=K, use_kernel=True)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    res = router.route_batch(np.arange(B), qemb, budget)
    res_k = router_k.route_batch(np.arange(B), qemb, budget)
    np.testing.assert_array_equal(res.predictions, res_k.predictions)
    np.testing.assert_allclose(res.costs, res_k.costs, rtol=1e-12, atol=0)
    assert res.arms_used == res_k.arms_used


class TestCompileBudget:
    """CompileSentinel: the wave program's XLA cache is keyed only by
    bucket shapes, so steady-state traffic never recompiles."""

    def test_route_batch_content_change_does_not_recompile(self):
        from repro.analysis import CompileSentinel, compile_cache_size
        from repro.serving import router as router_mod

        K, L, clusters, B, seed = 4, 8, 5, 96, 3
        wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
        levels = np.quantile(engine.costs, [0.3, 0.8]) * 2.5
        rng = np.random.default_rng(seed + 5)
        sentinel = CompileSentinel({"wave": router_mod._wave_scan})
        router.route_batch(np.arange(B), qemb, rng.choice(levels, size=B))
        # the program is in cache (earlier tests may have warmed this
        # bucket already, so assert the absolute population, not the delta)
        assert compile_cache_size(router_mod._wave_scan) >= 1
        sentinel.snapshot()
        # fresh queries and budget assignments, identical bucket shapes:
        # zero new XLA programs
        for s in (101, 102, 103):
            rng2 = np.random.default_rng(s)
            _, qemb2, _ = wl.sample_queries(B, rng2)
            router.route_batch(
                np.arange(B), qemb2, rng2.choice(levels, size=B)
            )
        sentinel.assert_no_new_compiles(
            detail="route_batch content change within one (B, T) bucket"
        )

    def test_route_batch_bucket_sharing_across_batch_sizes(self):
        from repro.analysis import CompileSentinel
        from repro.serving import router as router_mod

        K, L, clusters, B, seed = 4, 8, 5, 96, 3
        wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
        budget = float(np.quantile(engine.costs, 0.6)) * 2
        sentinel = CompileSentinel({"wave": router_mod._wave_scan})
        # 40 and 48 quantise to the same wave bucket: one compile serves both
        router.route_batch(np.arange(40), qemb[:40], budget)
        after_first = sentinel.compiles("wave")
        router.route_batch(np.arange(48), qemb[:48], budget)
        assert sentinel.compiles("wave") == after_first, (
            "B=40 and B=48 share a bucket; the second size must be a "
            "cache hit"
        )
        sentinel.assert_within(
            {"wave": 2}, detail="declared wave-bucket budget for one pool"
        )


def _symmetric_router(p_sym=0.8, N=200):
    """Two equal-cost, equal-p arms that always vote class 0 and class 1:
    every routed query ends in an exact belief tie."""
    emb = np.zeros((N, 4))
    table = np.zeros((N, 2))
    table[: int(N * p_sym)] = 1.0  # p_hat exactly p_sym for both arms
    est = SuccessProbEstimator(table, emb, np.zeros(N, np.int64))
    B = 64
    engine = PoolEngine(
        [
            TabularArm("zero", 1.0, np.zeros(B, np.int64)),
            TabularArm("one", 1.0, np.ones(B, np.int64)),
        ]
    )
    router = ThriftRouter(engine, est, num_classes=2)
    budget = 2.0
    # pin the selection to both arms so the wavefront really invokes both
    # (p_sym > 2/3 makes the empty-class belief positive, defeating early stop)
    cid = list(est.clusters)[0]
    p = est.clusters[cid].p_hat
    key = (np.round(np.asarray(p, np.float64), 12).tobytes(), 2, budget)
    router.selector._cache[key] = SelectionResult(
        chosen=np.asarray([0, 1], np.int64), xi_est=p_sym, cost=2.0, budget=budget
    )
    return router, np.zeros((B, 4)), budget, B


def test_tie_break_regression_symmetric_pool():
    """Seed bug: bare np.argmax biased every tied query to class 0."""
    router, qemb, budget, B = _symmetric_router()
    rng = np.random.default_rng(0)
    res = router.route_batch(np.arange(B), qemb, budget, rng=rng)
    assert all(len(a) == 2 for a in res.arms_used)  # both arms really invoked
    frac0 = float(np.mean(res.predictions == 0))
    assert 0.25 < frac0 < 0.75  # ~Binomial(64, 1/2); not systematically 0
    # deterministic mode stays reproducible: first-max tie break
    res_det = router.route_batch(np.arange(B), qemb, budget)
    assert (res_det.predictions == 0).all()


def test_tie_break_helper_scalar_and_batch():
    beliefs = np.array([[1.0, 1.0, 0.5], [0.2, 0.9, 0.9]])
    pred, ties = tie_break_argmax(beliefs)
    np.testing.assert_array_equal(pred, [0, 1])
    np.testing.assert_array_equal(ties, [2, 2])
    rng = np.random.default_rng(1)
    draws = [int(tie_break_argmax(beliefs[0], rng)[0]) for _ in range(300)]
    assert set(draws) == {0, 1}
    assert 0.4 < np.mean(draws) < 0.6


def test_scheduler_group_accounting_and_used_arm_latency():
    wl = OracleWorkload(num_classes=4, num_clusters=4, num_arms=8, seed=3)
    T, emb, _ = wl.response_table(400)
    assign, _ = kmeans(emb, 4, seed=0)
    est = SuccessProbEstimator(T, emb, assign)
    from repro.serving import OracleArm

    engine = PoolEngine([OracleArm(f"a{i}", wl, i, seed=11) for i in range(8)])
    router = ThriftRouter(engine, est, num_classes=4)
    sched = BatchScheduler(router, max_batch=16, max_wait_s=0.0)
    rng = np.random.default_rng(5)
    cid, qemb, lab = wl.sample_queries(16, rng)
    lo = float(np.quantile(engine.costs, 0.3)) * 2
    hi = float(np.quantile(engine.costs, 0.8)) * 2
    for i in range(16):
        sched.submit(
            Request(
                payload=(cid[i], lab[i]),
                embedding=qemb[i],
                budget=lo if i % 2 == 0 else hi,
            )
        )
    out = sched.flush()
    assert len(out) == 1
    batch, res = out[0]
    assert len(batch) == 16
    assert sched.stats["batches"] == 2       # two budget groups routed
    assert sched.stats["flushes"] == 1
    lat = sched.mitigator.history[-1]
    unused = res.arm_query_counts == 0
    assert (lat[unused] == 0.0).all()        # idle arms record no latency
    assert (lat[~unused] > 0.0).all()
    # per-query budgets enforced per group
    budgets = np.asarray([r.budget for r in batch])
    assert (res.costs <= budgets + 1e-12).all()


# ---------------------------------------------------------------------------
# The packed wave upload: one host buffer per routed group
# ---------------------------------------------------------------------------


def _random_wave_group(rng, T, B, K, L, faults):
    """One group's unpadded wave tables as `PendingRoute` stages them:
    plans of ragged depth (unscheduled cells, -inf residuals past each
    plan's end), responses drawn even on unscheduled cells as the pooled
    gather does, and under ``faults`` a non-identity failover gather."""
    from repro.distributed.fault import failover_gather

    depth = rng.integers(0, T + 1, B)
    live = np.arange(T)[:, None] < depth[None, :]
    sched = np.where(live, rng.integers(0, L, (T, B)), -1)
    resp = rng.integers(-1, K, (T, B))
    w = np.where(live, rng.random((T, B)) * 3.0, 0.0)
    tail = np.cumsum(w[::-1], axis=0)[::-1]
    res = np.where(live, np.log(tail + 1e-3) - 1.0, -np.inf)
    if faults:
        src, valid, _, _ = failover_gather(sched, rng.random((T, B)) < 0.3)
        assert (src != np.arange(T)[:, None])[valid].any()
    else:
        src = np.broadcast_to(np.arange(T, dtype=np.int32)[:, None], (T, B))
        valid = sched >= 0
    empty = -rng.random(B)
    return sched, resp, w, res, src, valid, empty


def _padded_tables(sched, resp, w, res, src, valid, empty):
    """The seven separate padded tables of the unpacked layout."""
    from repro.serving.router import _bucket

    T, B = sched.shape
    Tp, Bp = _bucket(T, base=4), _bucket(B, base=8)

    def pad(x, fill, dtype):
        out = np.full((Tp, Bp), fill, dtype)
        out[:T, :B] = x
        return out

    src_p = np.broadcast_to(np.arange(Tp, dtype=np.int32)[:, None], (Tp, Bp)).copy()
    src_p[:T, :B] = src
    empty_p = np.zeros(Bp)
    empty_p[:B] = empty
    return (pad(sched, -1, np.int32), pad(resp, -1, np.int32),
            pad(w, 0.0, np.float64), pad(res, -np.inf, np.float64), src_p,
            pad(valid, False, bool), empty_p)


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("faults", [False, True], ids=["identity", "failover"])
@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_packed_wave_program_is_bit_identical(use_kernel, faults):
    """The packed program (`_wave_scan` over `_pack_wave`) returns exactly
    what `_wave_scan_core` returns on the seven unpacked tables, on groups
    whose B and T are not bucket multiples."""
    import functools

    import jax

    from repro.core.selection import STOP_MARGIN
    from repro.core.x64 import x64
    from repro.serving import router as router_mod

    unpacked = functools.partial(
        jax.jit, static_argnames=("num_classes", "use_kernel")
    )(router_mod._wave_scan_core)
    rng = np.random.default_rng(15 + 2 * use_kernel + faults)
    for T, B, K, margin in ((3, 13, 4, STOP_MARGIN), (9, 51, 4, STOP_MARGIN),
                            (6, 37, 5, 0.3)):
        tables = _random_wave_group(rng, T, B, K, 12, faults)
        kw = dict(num_classes=K, use_kernel=use_kernel)
        with x64(), router_mod._quiet_donation():
            want = unpacked(*_padded_tables(*tables), margin, **kw)
            for scan in (router_mod._wave_scan, router_mod._wave_scan_nodonate):
                got = scan(router_mod._pack_wave(*tables, margin), **kw)
                for g, e in zip(got, want):
                    assert g.dtype == e.dtype and g.shape == e.shape
                    np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


@pytest.mark.parametrize("entry", ["route_batch", "scheduler", "reference"])
def test_one_route_places_one_wave_buffer(entry):
    """A jit-plane group is one upload of its packed buffer's bytes,
    mirrored into `BatchScheduler.stats`; the reference plane uploads
    nothing."""
    from repro.serving.router import _WAVE_PLANES, _bucket

    K, L, clusters, B, seed = 4, 8, 5, 51, 3
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    if entry == "reference":
        router = ThriftRouter(engine, est, num_classes=K, jit_waves=False)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    before = router.upload_stats()
    if entry == "scheduler":
        sched = BatchScheduler(router, max_batch=B, max_wait_s=0.0)
        sched.submit_many(np.arange(B), qemb, budget)
        sched.drain()
        stats = sched.stats
        assert stats["spec_jit"] == 1
        assert {k: stats[k] for k in before} == router.upload_stats()
    res = router.route_batch(np.arange(B), qemb, budget)   # the same group
    after = router.upload_stats()
    if entry == "reference":
        assert after == before
        return
    n = 2 if entry == "scheduler" else 1
    Tp, Bp = _bucket(res.schedule.shape[1], base=4), _bucket(B, base=8)
    assert after["wave_uploads"] - before["wave_uploads"] == n
    assert (after["wave_upload_bytes"] - before["wave_upload_bytes"]
            == n * (_WAVE_PLANES * Tp + 2) * Bp * 8)


def test_prewarmed_buckets_serve_a_burst_without_compiling():
    """`prewarm_compile` packs and places exactly as traffic does, so a
    served burst of ragged groups after it compiles nothing."""
    from repro.analysis import CompileSentinel
    from repro.serving import router as router_mod

    K, L, clusters, B, seed = 4, 8, 5, 160, 19
    wl, est, engine, router, qemb, R = _make_pool(K, L, clusters, B, seed)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    router.prewarm_compile(32, all_batch_buckets=True)
    sentinel = CompileSentinel({"wave": router_mod._wave_scan})
    sentinel.snapshot()
    sched = BatchScheduler(router, max_batch=32, max_wait_s=0.0)
    lo = 0
    for n in (32, 7, 19, 32, 1, 25, 32, 12):
        sched.submit_many(np.arange(lo, lo + n), qemb[lo:lo + n], budget)
        sched.drain()
        lo += n
    assert sched.stats["spec_jit"] >= 8
    sentinel.assert_no_new_compiles(detail="served burst after prewarm_compile")
