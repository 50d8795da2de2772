"""Serving runtime: router correctness, budget enforcement, scheduler."""
import numpy as np
import pytest

from repro.core.clustering import kmeans
from repro.core.estimation import SuccessProbEstimator
from repro.data import OracleWorkload
from repro.serving import BatchScheduler, OracleArm, PoolEngine, Request, ThriftRouter


@pytest.fixture(scope="module")
def setup():
    wl = OracleWorkload(num_classes=4, num_clusters=5, num_arms=8, seed=3)
    T, emb, cid = wl.response_table(600)
    assign, _ = kmeans(emb, 5, seed=0)
    est = SuccessProbEstimator(T, emb, assign)
    engine = PoolEngine([OracleArm(f"a{i}", wl, i, seed=11) for i in range(8)])
    router = ThriftRouter(engine, est, num_classes=4)
    return wl, est, engine, router


def _queries(wl, n, seed=42):
    rng = np.random.default_rng(seed)
    cid, emb, lab = wl.sample_queries(n, rng)
    return list(zip(cid, lab)), emb, lab


def test_router_respects_per_query_budget(setup):
    wl, est, engine, router = setup
    queries, emb, lab = _queries(wl, 200)
    for budget in np.quantile(engine.costs, [0.2, 0.5, 0.9]):
        res = router.route_batch(queries, emb, float(budget) * 2)
        assert (res.costs <= float(budget) * 2 + 1e-12).all()
        assert (res.costs <= res.planned_costs + 1e-12).all()


def test_router_beats_cheapest_single_arm(setup):
    wl, est, engine, router = setup
    queries, emb, lab = _queries(wl, 400)
    budget = float(np.quantile(engine.costs, 0.7)) * 2
    res = router.route_batch(queries, emb, budget)
    acc = (res.predictions == lab).mean()
    # cheapest arm alone
    rng = np.random.default_rng(9)
    cheap = np.argmin(engine.costs)
    acc_cheap = np.mean(
        [wl.invoke(int(cheap), int(c), int(l), rng) == l for c, l in queries]
    )
    assert acc > acc_cheap + 0.02


def test_router_accuracy_tracks_xi_estimate(setup):
    wl, est, engine, router = setup
    queries, emb, lab = _queries(wl, 500)
    budget = float(np.quantile(engine.costs, 0.8)) * 3
    res = router.route_batch(queries, emb, budget)
    acc = (res.predictions == lab).mean()
    assert acc > 0.85


def test_wavefront_stops_early_on_consensus(setup):
    """Easy clusters should not invoke every selected arm."""
    wl, est, engine, router = setup
    queries, emb, lab = _queries(wl, 200)
    budget = float(engine.costs.sum())  # everything affordable
    res = router.route_batch(queries, emb, budget)
    n_used = np.array([len(a) for a in res.arms_used])
    planned = res.planned_costs
    assert (res.costs <= planned + 1e-12).all()
    assert n_used.mean() > 0


def test_scheduler_batches_and_routes(setup):
    wl, est, engine, router = setup
    queries, emb, lab = _queries(wl, 64)
    sched = BatchScheduler(router, max_batch=16, max_wait_s=0.0)
    budget = float(np.quantile(engine.costs, 0.6)) * 2
    for q, e in zip(queries, emb):
        sched.submit(Request(payload=q, embedding=e, budget=budget))
    total = 0
    while sched.ready():
        for group, res in sched.flush():
            total += len(group)
            assert (res.costs <= budget + 1e-12).all()
    assert total == 64
    assert sched.stats["batches"] == 4


def test_straggler_hedge_plan(setup):
    _, _, _, router = setup
    sched = BatchScheduler(router)
    plan = sched.mitigator.hedge_plan([3, 1, 5], slow_arm=1)
    assert plan == [3, 5, 1]


@pytest.mark.parametrize("env", ["/srv/jax-cache", None])
def test_compile_cache_placed_by_env_or_repo(env, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, where set, rules and nothing is changed;
    otherwise the cache goes to the fixed `.jax_cache` at the repo root."""
    import pathlib

    import jax
    from repro.serving import compile_cache

    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        compile_cache.configure_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo_cache = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
    assert compile_cache.CACHE_DIR == repo_cache
    assert got == (before if env else str(repo_cache))
