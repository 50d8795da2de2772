"""Smoke run of the served path on a TPU, in one process.

    python chip_smoke.py               # one chip: the main path end to end
    python chip_smoke.py --four-chips  # four chips: the replica plane only

One chip: the launcher's own deployment (``repro.launch.serve``: 12 oracle
arms, K=4 classes, 6 clusters, budget 1e-4, a 2000-query history) is
calibrated and clustered, its plans and wave programs are warmed, and a few
thousand queries are served through ``BatchScheduler.submit_many``/``drain``.
The run then checks, and fails on any miss:

* every group took the jit plane;
* every request matches ``route_batch_reference`` on the same payloads:
  prediction, stop wave and cost;
* one batched replan (``PlanService.plan_many``) picks the same arm sets as
  serial ``sur_greedy`` per (cluster, budget) pair;
* nothing compiled inside the served window;
* the wave program's outputs live on the TPU;
* both serving kernels run natively and match ``repro.kernels.ref``.

Four chips: ``ReplicaSet(replicas=4)`` serves the same traffic with
``placement="overlapped"`` and then ``placement="fused"``; the two must agree
per request, and the overlapped workers' outputs must sit on four distinct
chips.

Each arm's answer to each query is drawn once from the workload, so every
plane sees the same answers and can be compared request by request. The
timings printed on the way are one smoke run's readings, not a benchmark.
The last line of a passing run is a JSON object naming the device; a run
that finds no TPU, or fails a check, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

QUERIES = 4096


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def deployment(seed: int):
    """The launcher's deployment and ``QUERIES`` queries of its traffic.

    Returns ``(router, payloads, embeddings)``; a payload row is (cluster,
    label, query index), and each arm's answer to each query is drawn once
    up front, so the answer an arm gives never depends on which plane asks.
    """
    from repro.launch.serve import DEPLOYMENT, build_deployment
    from repro.serving import PoolEngine, ThriftRouter

    class FixedAnswers(PoolEngine):
        def __init__(self, arms, answers):
            super().__init__(arms)
            self.answers = answers                 # (L, N) class ids

        def invoke_grid(self, sched_T, payloads):
            return self.answers[np.maximum(sched_T, 0), payloads[None, :, 2]]

        def invoke_rows(self, arm_ids, queries, rows):
            q = np.asarray(queries, np.int64)
            return self.answers[np.asarray(arm_ids, np.int64),
                                q[np.asarray(rows, np.int64), 2]]

    wl, base = build_deployment(**DEPLOYMENT)
    rng = np.random.default_rng(seed)
    cid, qemb, labels = wl.sample_queries(QUERIES, rng)
    payloads = np.column_stack([cid, labels, np.arange(QUERIES)])
    answers = np.stack([
        wl.invoke_batch(a, cid, labels, rng)
        for a in range(len(base.engine.arms))
    ])
    engine = FixedAnswers(base.engine.arms, answers)
    router = ThriftRouter(engine, base.estimator, num_classes=base.num_classes)
    return router, payloads, qemb


def wave_output_devices(router, payloads, qemb, budget) -> set:
    """Devices holding the outputs of one jit-plane dispatch."""
    pending = router.begin_route(payloads, qemb, budget, mode="jit")
    devices = set(pending._dev[0].devices())
    pending.result()
    return devices


def one_chip(jax) -> None:
    from repro.analysis import CompileSentinel
    from repro.core import selection as selection_mod
    from repro.core.mc import GroupedXiEstimator
    from repro.core.selection import sur_greedy
    from repro.kernels import ops, ref
    from repro.launch.serve import BUDGET, MAX_BATCH
    from repro.serving import BatchScheduler, PlanService
    from repro.serving import router as router_mod

    t0 = time.perf_counter()
    router, payloads, qemb = deployment(seed=1)
    sched = BatchScheduler(router, max_batch=MAX_BATCH, max_wait_s=0.002)
    plans = sched.prewarm(budgets=[BUDGET])
    buckets = router.prewarm_compile(max_batch=MAX_BATCH, all_batch_buckets=True)
    setup_s = time.perf_counter() - t0
    print(f"smoke run (not a benchmark): set-up {setup_s:.1f}s, "
          f"{plans} plans, {buckets} wave buckets compiled")

    sentinel = CompileSentinel({
        "wave": router_mod._wave_scan, "plan": selection_mod._sur_greedy_scan,
    })
    t0 = time.perf_counter()
    blk = sched.submit_many(payloads, qemb, BUDGET)
    sched.drain()
    served_s = time.perf_counter() - t0
    recompiles = sentinel.total()
    lat = sched.latency_stats()
    st = sched.stats
    print(f"smoke run: served {QUERIES} queries in {served_s:.3f}s "
          f"({QUERIES / served_s:.0f} qps), p50 {1e3 * lat['p50_s']:.2f}ms "
          f"p99 {1e3 * lat['p99_s']:.2f}ms, groups jit={st['spec_jit']} "
          f"reference={st['spec_reference']}, recompiles {recompiles}")
    check(st["spec_jit"] > 0 and st["spec_reference"] == 0,
          "a group left the jit plane")
    check(recompiles == 0, f"compiles in the served window: {sentinel.deltas()}")

    want = router.route_batch_reference(payloads, qemb, BUDGET)
    differ = np.flatnonzero(
        (blk.predictions != want.predictions)
        | (blk.stop_waves != want.stop_waves)
        | (blk.costs != want.costs)
    )
    print(f"jit plane vs route_batch_reference: {differ.size} of {QUERIES} "
          f"requests differ (prediction, stop wave or cost)")
    check(differ.size == 0, f"requests {differ[:20].tolist()} differ")

    # one batched replan over every (cluster, budget) pair vs serial
    selector = dataclasses.replace(router.selector)
    service = PlanService(selector, router.estimator, router.engine,
                          router.num_classes)
    pairs = [(c, BUDGET) for c in sorted(router.estimator.clusters)]
    batched = service.plan_many(pairs)
    same = 0
    for (c, budget), plan in zip(pairs, batched):
        p = router.estimator.clusters[c].p_hat
        serial = sur_greedy(p, router.engine.costs, budget, router.num_classes,
                            jax.random.key(selector.seed),
                            selector.theta(p, budget))
        same += sorted(plan.order.tolist()) == sorted(serial.chosen.tolist())
    print(f"batched replan vs serial sur_greedy: {same} of {len(pairs)} "
          f"(cluster, budget) pairs pick the same arms")
    check(same == len(pairs), "batched plans differ from serial sur_greedy")

    devices = wave_output_devices(router, payloads[:MAX_BATCH],
                                  qemb[:MAX_BATCH], BUDGET)
    print(f"wave program outputs on {sorted(str(d) for d in devices)}")
    check({d.platform for d in devices} == {"tpu"},
          "wave outputs are not on the TPU")

    # both serving kernels, natively, at the served shapes, against the
    # plain references evaluated on the host CPU
    check(not ops._interpret(), "kernels would run in interpret mode")
    cpu = jax.devices("cpu")[0]
    K, L = router.num_classes, len(router.engine.arms)
    rows = MAX_BATCH * (L + 1)          # the wave program's prefix expansion
    rng = np.random.default_rng(3)
    resp = rng.integers(-1, K, (rows, L)).astype(np.int32)
    w = rng.uniform(0.3, 3.0, (rows, L)).astype(np.float32)
    empty = np.full(rows, -1.5, np.float32)
    got_b, got_p = ops.belief_aggregate(resp, w, empty, K, tile=512)
    with jax.default_device(cpu):
        want_b, want_p = ref.belief_aggregate_ref(resp, w, empty, K)
    bel_err = float(np.max(np.abs(np.asarray(got_b) - np.asarray(want_b))))
    check(bel_err <= 1e-6 and np.array_equal(got_p, want_p),
          f"belief_aggregate off its reference by {bel_err}")

    ps = np.stack([router.estimator.clusters[c].p_hat for c, _ in pairs])
    thetas = [selector.theta(p, BUDGET) for p in ps]
    est = GroupedXiEstimator(jax.random.key(1), ps, K, thetas)
    masks = (rng.random((len(pairs), 3, L)) < 0.6).astype(np.float32)
    got_xi = ops.mc_correctness_grouped(
        est.responses, masks, est.log_weights, est.empty, est.valid,
        est.theta_f.astype(np.float32), K,
    )
    with jax.default_device(cpu):
        want_xi = ref.mc_correctness_grouped_ref(
            est.responses, masks, est.log_weights, est.empty, est.valid,
            est.theta_f, K,
        )
    xi_err = float(np.max(np.abs(np.asarray(got_xi) - np.asarray(want_xi))))
    check(xi_err <= 2e-6, f"mc_correctness_grouped off its reference by {xi_err}")
    print(f"kernels native on {jax.devices()[0].platform}: belief_aggregate "
          f"({rows}x{L}, K={K}) max err {bel_err:.3g}; mc_correctness_grouped "
          f"(G={len(pairs)}, theta<={max(thetas)}, L={L}) max err {xi_err:.3g}")


def four_chips(jax) -> None:
    from repro.launch.serve import BUDGET, MAX_BATCH
    from repro.serving import ReplicaSet

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    blocks = {}
    for placement in ("overlapped", "fused"):
        router, payloads, qemb = deployment(seed=1)
        rset = ReplicaSet(router, replicas=4, max_batch=MAX_BATCH,
                          max_wait_s=0.002, placement=placement)
        t0 = time.perf_counter()
        rset.prewarm(budgets=[BUDGET])
        rset.prewarm_compile()
        setup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        blk = rset.submit_many(payloads, qemb, BUDGET)
        rset.drain()
        served_s = time.perf_counter() - t0
        blocks[placement] = blk
        print(f"smoke run (not a benchmark): {placement} R=4 set-up "
              f"{setup_s:.1f}s, served {QUERIES} queries in {served_s:.3f}s "
              f"({QUERIES / served_s:.0f} qps)")
        if placement == "overlapped":
            devices = set()
            for wk in rset.workers:
                devices |= wave_output_devices(
                    wk.router, payloads[:MAX_BATCH], qemb[:MAX_BATCH], BUDGET
                )
            print(f"overlapped workers' wave outputs on "
                  f"{sorted(str(d) for d in devices)}")
            check(len(devices) == 4 and {d.platform for d in devices} == {"tpu"},
                  "overlapped workers do not sit on four distinct chips")
    o, f = blocks["overlapped"], blocks["fused"]
    differ = np.flatnonzero(
        (o.predictions != f.predictions) | (o.stop_waves != f.stop_waves)
        | (o.costs != f.costs)
    )
    print(f"overlapped vs fused: {differ.size} of {QUERIES} requests differ")
    check(differ.size == 0, f"requests {differ[:20].tolist()} differ")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip replica plane")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    from repro.serving import configure_compile_cache

    configure_compile_cache()
    print(f"device: {dev.device_kind} x{len(jax.devices())}")
    (four_chips if args.four_chips else one_chip)(jax)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
