"""Serving throughput: jitted wave loop vs wavefront (PR 1) vs seed router,
plus the continuous-batching front-end under a steady-state arrival process.

Sweeps batch sizes on an oracle pool and reports queries/sec plus realized-
vs-planned cost for three engines:

  * ``jit``       — ``ThriftRouter.route_batch`` (PR 2): the whole wave loop
                    as one on-device ``lax.scan`` behind the plan cache;
  * ``wavefront`` — ``ThriftRouter.route_batch_reference`` (PR 1): the
                    compacting host-side wavefront;
  * ``seed``      — a faithful reproduction of the seed implementation
                    (per-query Python belief updates in the wave loop AND a
                    per-query Python loop inside the oracle arm).

Then drives the same pool through the :class:`BatchScheduler` front-end
(``steady_state`` in the report): a saturated run measuring end-to-end
capacity at batch-256 admission (submit -> admission queue -> pipelined
budget-group waves -> futures), and a Poisson arrival run at a fraction of
that capacity recording per-request p50/p99 completion latency.

The ``replica_scaling`` section measures the R-replica serving plane
(``ReplicaSet``): aggregate qps and p99 completion tails for R in {1, 2, 4}
at a fixed per-replica admission batch on one saturated stream — sharded
affinity admission plus single-device fused same-budget wave dispatch —
with the R=1 row bit-checked against the plain ``BatchScheduler`` steady
path (the committed full-size report carries the >= 2x aggregate qps at
R=4 acceptance bar). Its ``cross_device`` subsection adds the multi-device
placement curve (run under ``XLA_FLAGS=--xla_force_host_platform_device_
count=4``): overlapped per-device wave dispatch vs fused single-device
dispatch at the serving level AND at the raw wave-program level, with an
explicit ``parallel_capable`` flag — forced host devices multiplex the
host's physical cores, so the >= 1.5x overlapped-vs-fused bar is only
asserted where the host can actually run device programs concurrently.

The ``selection`` section measures the batched planner (PR 5): serial vs
batched replan latency when G in {1, 8, 64} drifted clusters re-select at
once, with bit-identical plans asserted across the two paths (the
committed full-size report carries the >= 3x speedup acceptance bar at
G = 64).

The ``raw_speed`` section is the PR 10 pass: the fully on-device planner
(greedy-on-gamma, l* and candidate scoring fused into the scan program,
``sur_greedy_many``) against the retained PR 9 host-gamma plane at G in
{1, 8, 64} with bit-identical plans asserted (the committed report carries
the >= 1.3x bar at G = 64); donated vs non-donated wave dispatch with the
routes bit-checked.

Finally the ``feedback`` section measures the online estimation loop on
synthetic *drifted* traffic: the arms the served plans rely on degrade
mid-stream, and three pipelines route the same post-drift request stream —
frozen plans (no feedback), the feedback-enabled front-end (ground-truth
labels recorded per chunk, folded at admission boundaries, drift-gated
replans), and an oracle replan (re-estimated from post-drift truth). The
acceptance bar: online recovers >= 90% of the oracle's drifted-cluster
tail accuracy while frozen does not; ``overhead_vs_frozen`` reports the
wall-time cost of carrying the loop.

Writes ``BENCH_serving.json``; if the output file already holds an earlier
report, its summary is appended to ``history`` so the perf trajectory
(seed -> wavefront -> jitted -> continuous) stays in one file.

Run:  PYTHONPATH=src python -m benchmarks.serving_throughput [--out BENCH_serving.json]
CI smoke:  python -m benchmarks.serving_throughput --smoke --out /tmp/bench.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import List

import jax
import numpy as np

from repro.analysis import CompileSentinel, compile_cache_size
from repro.core import selection as selection_mod
from repro.core.belief import empty_log_belief, log_weight
from repro.core.clustering import kmeans
from repro.core.estimation import SuccessProbEstimator
from repro.core.mc import bucket_size
from repro.core.types import clip_probs
from repro.data import OracleWorkload
from repro.distributed.fault import FaultPolicy
from repro.serving import (
    BatchScheduler,
    OracleArm,
    PoolEngine,
    ThriftRouter,
    configure_compile_cache,
)
from repro.serving import router as router_mod

BATCH_SIZES = [32, 64, 128, 256, 512, 1024]


@dataclasses.dataclass
class _SeedOracleArm:
    """Seed-commit oracle arm: one workload.invoke per query (Python loop)."""

    name: str
    workload: OracleWorkload
    arm_index: int
    seed: int = 0

    def __post_init__(self):
        self.cost = float(self.workload.costs[self.arm_index])
        self._rng = np.random.default_rng(self.seed + 7919 * self.arm_index)

    def classify_batch(self, queries) -> np.ndarray:
        out = np.empty(len(queries), np.int64)
        for i, (cid, label) in enumerate(queries):
            out[i] = self.workload.invoke(self.arm_index, cid, label, self._rng)
        return out

    def latency_s(self, batch: int) -> float:
        return 0.0


def _seed_lookup_batch(est: SuccessProbEstimator, embeddings: np.ndarray) -> np.ndarray:
    """Seed-commit lookup_batch: full (B, C, d) difference tensor."""
    d = ((embeddings[:, None, :] - est._centroids[None, :, :]) ** 2).sum(-1)
    return est._cids[np.argmin(d, axis=1)]


def seed_route_batch(router: ThriftRouter, engine: PoolEngine, queries, embeddings, budget):
    """The seed ``ThriftRouter.route_batch``, verbatim modulo imports: per-
    cluster groups routed serially, per-query Python loops updating beliefs."""
    B = len(queries)
    K = router.num_classes
    cluster_ids = _seed_lookup_batch(router.estimator, embeddings)

    predictions = np.zeros(B, np.int64)
    costs = np.zeros(B, np.float64)
    planned = np.zeros(B, np.float64)
    arms_used: List[List[int]] = [[] for _ in range(B)]

    for cid in np.unique(cluster_ids):
        q_idx = np.flatnonzero(cluster_ids == cid)
        stats = router.estimator.clusters[int(cid)]
        p = stats.p_hat
        sel = router.selector.select(p, K, budget)
        order = sorted(sel.chosen, key=lambda i: -p[i])
        w = log_weight(clip_probs(p), K)
        empty = empty_log_belief(p)

        nb = q_idx.size
        beliefs = np.full((nb, K), empty, np.float64)
        counts = np.zeros((nb, K), np.int64)
        active = np.ones(nb, bool)
        planned[q_idx] = float(engine.costs[order].sum()) if order else 0.0

        for wave, arm in enumerate(order):
            log_f = float(np.sum(w[order[wave:]]))
            srt = np.sort(beliefs, axis=1)
            h1, h2 = srt[:, -1], srt[:, -2]
            still = active & (log_f + h2 > h1 - 1e-9)
            if not still.any():
                break
            full_active = np.zeros(B, bool)
            full_active[q_idx[still]] = True
            resp = engine.invoke_arm(arm, queries, full_active)[q_idx]
            hit = np.flatnonzero(still)
            for j in hit:
                r = int(resp[j])
                if counts[j, r] == 0:
                    beliefs[j, r] = w[arm]
                else:
                    beliefs[j, r] += w[arm]
                counts[j, r] += 1
                costs[q_idx[j]] += engine.costs[arm]
                arms_used[q_idx[j]].append(arm)
            active = still

        predictions[q_idx] = np.argmax(beliefs, axis=1)
    return predictions, costs, planned


def steady_state(router, wl, budget: float, batch: int, n_queries: int,
                 load: float, seed: int = 23, repeats: int = 5) -> dict:
    """Drive the continuous-batching front-end and measure it end to end.

    Two runs over the same request stream:

    * **saturated** — every request submitted at t0 (offered load far above
      capacity): measures the front-end's sustainable throughput at
      ``batch``-sized admission, i.e. the one-shot jitted engine plus all
      scheduler overhead (admission, budget grouping, pipelined dispatch,
      future resolution). Best-of-``repeats``, like the one-shot engine
      rows, since this is the number the acceptance bar compares against
      the raw jitted engine.
    * **steady** — Poisson arrivals at ``load``x the measured capacity:
      below saturation, so the p50/p99 completion latencies reflect
      queueing + batching delay rather than unbounded backlog.
    """
    from repro.serving.router import _bucket

    rng = np.random.default_rng(seed)
    cid, qemb, lab = wl.sample_queries(n_queries, rng)
    payloads = np.column_stack([cid, lab])

    coalesce = 4

    def make_sched():
        return BatchScheduler(
            router, max_batch=batch, max_wait_s=0.0005, max_inflight=2,
            coalesce=coalesce,
        )

    # warm-up: fill plan caches and compile the wave program for every
    # (B,) bucket an admission could land in — partial bursts from the
    # arrival run up through saturation-coalesced batches
    warm = make_sched()
    for b in sorted({
        _bucket(n, base=8) for n in range(1, coalesce * batch + 1)
    }):
        b = min(b, n_queries)
        warm.submit_many(payloads[:b], qemb[:b], budget)
        warm.drain()

    # saturated capacity, paired with a bare-engine measurement of the SAME
    # stream in `batch`-sized one-shot calls, interleaved (best-of each) so
    # shared-host load spikes penalize both sides equally — this ratio is
    # the "front-end overhead vs the PR 2 jitted engine" acceptance number
    dt = dt_oneshot = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        for s in range(0, n_queries, batch):
            router.route_batch(
                payloads[s:s + batch], qemb[s:s + batch], budget
            )
        dt_oneshot = min(dt_oneshot, time.perf_counter() - t0)
        sched = make_sched()
        t0 = time.perf_counter()
        blk = sched.submit_many(payloads, qemb, budget)
        sched.drain()
        dt = min(dt, time.perf_counter() - t0)
    saturated_qps = n_queries / dt
    oneshot_qps = n_queries / dt_oneshot
    accuracy = float((blk.predictions == lab).mean())

    # steady arrival process at `load` x capacity
    offered_qps = load * saturated_qps
    sched2 = make_sched()
    start = time.monotonic()
    arrivals = start + np.cumsum(rng.exponential(1.0 / offered_qps, n_queries))
    sent = 0
    while sent < n_queries:
        now = time.monotonic()
        due = int(np.searchsorted(arrivals, now, side="right"))
        if due > sent:
            sched2.submit_many(
                payloads[sent:due], qemb[sent:due], budget,
                arrival_s=arrivals[sent:due],
            )
            sent = due
        sched2.pump()
    sched2.drain()
    steady_dt = time.monotonic() - start
    lat = sched2.latency_stats()

    return {
        "max_batch": batch,
        "queries": n_queries,
        "saturated_qps": saturated_qps,
        "oneshot_qps": oneshot_qps,
        "vs_jit_engine": saturated_qps / oneshot_qps,
        "offered_qps": offered_qps,
        "steady_qps": n_queries / steady_dt,
        "p50_ms": 1e3 * lat.get("p50_s", 0.0),
        "p99_ms": 1e3 * lat.get("p99_s", 0.0),
        "mean_ms": 1e3 * lat.get("mean_s", 0.0),
        "accuracy": accuracy,
        # scheduler counters of the Poisson run the latencies describe
        "flushes": int(sched2.stats["flushes"]),
        "groups": int(sched2.stats["batches"]),
        "spec_jit": int(sched2.stats["spec_jit"]),
        "spec_reference": int(sched2.stats["spec_reference"]),
        # and of the saturated-capacity run (coalesced admissions)
        "saturated_flushes": int(sched.stats["flushes"]),
        "saturated_groups": int(sched.stats["batches"]),
        "saturated_spec_jit": int(sched.stats["spec_jit"]),
        "saturated_spec_reference": int(sched.stats["spec_reference"]),
    }


def replica_scaling(router, wl, budget: float, per_batch: int, make_router,
                    replicas=(1, 2, 4), n_queries: int = 0, seed: int = 41,
                    repeats: int = 3) -> dict:
    """Aggregate throughput and completion tails of the R-replica plane.

    The SAME saturated request stream is served at fixed *per-replica*
    admission size by R in ``replicas``: sharded affinity admission, one
    fused same-budget wave dispatch per drive cycle on a single device
    (the multi-replica tentpole). Because every run serves an identical
    workload, higher R finishing sooner shows up as BOTH higher qps and an
    equal-or-better p99 — the acceptance bar is R=4 >= 2x the R=1 qps.

    The R=1 row is additionally bit-checked against the plain
    ``BatchScheduler`` steady path on the same stream
    (``r1_bitmatch_steady``): the replica front-end at R=1 must not cost
    or change anything. Oracle arms draw responses from a per-arm rng that
    advances with every invocation, so the check runs each side on its own
    freshly-seeded ``make_router()`` pool — the streams stay bit-equal
    exactly when the two front-ends invoke the same cells in the same
    order, which is the contract. All timed passes run after a warm-up pass plus
    ``prewarm_compile`` (per-replica and fused buckets), and a
    CompileSentinel asserts the timed section never compiles.

    Measurement notes: the per-replica admission size is deliberately
    small (latency-bound regime — that is where cross-replica fusion
    amortizes the per-dispatch host cost; at large per-replica batches a
    single scheduler is already amortized), ``spill_factor=1.0`` pins the
    shards to exact fair share so every drive cycle fuses all R workers,
    and the repeats are INTERLEAVED across R so machine noise hits every
    row under the same conditions before best-of is taken.
    """
    from repro.serving import ReplicaSet

    n = n_queries or per_batch * 128
    rng = np.random.default_rng(seed)
    cid, qemb, lab = wl.sample_queries(n, rng)
    payloads = np.column_stack([cid, lab])

    def make_set(R):
        # pinned to the fused placement: this sweep is the PR-8 historical
        # metric (admission-plane scaling with single-device fused waves);
        # the overlapped-vs-fused placement comparison lives in the
        # cross_device subsection
        return ReplicaSet(
            router, replicas=R, max_batch=per_batch, max_wait_s=0.0005,
            max_inflight=12, coalesce=1, spill_factor=1.0,
            placement="fused",
        )

    # warm every bucket the sweep can hit (per-replica + fused), then pin
    # the timed section to zero recompiles
    for R in replicas:
        rset = make_set(R)
        rset.prewarm(budgets=[budget])
        rset.prewarm_compile()
        rset.submit_many(payloads, qemb, budget)
        rset.drain()
    sentinel = CompileSentinel({"wave": router_mod._wave_scan})
    sentinel.snapshot()

    best = {}
    for _ in range(repeats):
        for R in replicas:
            rset = make_set(R)
            t0 = time.perf_counter()
            blk = rset.submit_many(payloads, qemb, budget)
            rset.drain()
            dt = time.perf_counter() - t0
            if R not in best or dt < best[R][0]:
                best[R] = (dt, rset, blk)

    rows = []
    r1_qps = None
    for R in replicas:
        best_dt, rset, blk = best[R]
        lat = rset.latency_stats()
        st = rset.stats
        qps = n / best_dt
        if R == replicas[0]:
            r1_qps = qps
        rows.append({
            "replicas": int(R),
            "per_replica_batch": per_batch,
            "qps": qps,
            "p50_ms": 1e3 * lat.get("p50_s", 0.0),
            "p99_ms": 1e3 * lat.get("p99_s", 0.0),
            "speedup_vs_r1": qps / r1_qps,
            "placement": rset.placement,
            "devices": int(st["replica_devices"]),
            "fused_dispatches": int(st["replica_fused"]),
            "fused_rows": int(st["replica_fused_rows"]),
            "overlapped_dispatches": int(st["replica_overlapped"]),
            "spills": int(st["replica_spills"]),
            "accuracy": float((blk.predictions == lab).mean()),
        })
        print(
            f"replica scaling R={R}: {qps:9.0f} qps "
            f"({rows[-1]['speedup_vs_r1']:4.2f}x R=1) | p99 "
            f"{rows[-1]['p99_ms']:7.2f}ms | fused {st['replica_fused']} "
            f"({st['replica_fused_rows']} rows) spills {st['replica_spills']}"
        )
    timed_recompiles = sentinel.total()

    # R=1 contract: bit-identical to the plain BatchScheduler steady path
    # (twin freshly-seeded pools: see the docstring)
    rset1 = ReplicaSet(make_router(), replicas=1, max_batch=per_batch,
                       max_wait_s=0.0005, max_inflight=12, coalesce=1)
    r1_blk = rset1.submit_many(payloads, qemb, budget)
    rset1.drain()
    base = BatchScheduler(make_router(), max_batch=per_batch,
                          max_wait_s=0.0005, max_inflight=12, coalesce=1)
    ref = base.submit_many(payloads, qemb, budget)
    base.drain()
    r1_bitmatch = bool(
        np.array_equal(r1_blk.predictions, ref.predictions)
        and np.array_equal(r1_blk.costs, ref.costs)
        and np.array_equal(r1_blk.stop_waves, ref.stop_waves)
    )
    by_r = {r["replicas"]: r for r in rows}
    top = max(by_r)
    return {
        "per_replica_batch": per_batch,
        "queries": n,
        "rows": rows,
        "r1_bitmatch_steady": r1_bitmatch,
        "speedup_at_max": by_r[top]["speedup_vs_r1"],
        "replicas_max": int(top),
        "timed_recompiles": int(timed_recompiles),
    }


def cross_device(router, wl, budget: float, per_batch: int, make_router,
                 replicas=(1, 2, 4), seed: int = 43, repeats: int = 3,
                 wave_rows_per_device: int = 4096) -> dict:
    """Cross-device scaling curve: overlapped-R-devices vs fused-1-device.

    Two layers, both at R in ``replicas`` on however many host devices the
    process was forced to (CI: ``--xla_force_host_platform_device_count=4``):

    * **serving rows** — the full ReplicaSet stream (admission, planning,
      speculative gather, dispatch, retirement) under
      ``placement="overlapped"`` vs ``placement="fused"``. End-to-end qps
      here is dominated by the single-threaded host front-end, so this
      layer mostly prices the placement's per-dispatch overhead.
    * **wave_plane rows** — the device-program curve the placement
      actually owns: identical pre-staged padded wave tables, R per-device
      ``_wave_scan`` programs in flight concurrently vs one fused
      ``R x rows`` program on a single device. No host work in the timed
      section beyond R dispatches.

    ``parallel_capable`` records whether the host can physically overlap
    device programs (``host_cores >= devices``). Forced host devices
    multiplex the same cores, so on a 1-core container the overlapped
    ratios sit below 1 — CI asserts the >= 1.5x acceptance bar only when
    ``parallel_capable`` is true, and always asserts well-formedness,
    the R=1 bit-match and the zero-recompile contract.

    Returns ``{"devices": 1, "skipped": true}`` on a single-device
    process (nothing to place across).
    """
    import os

    import jax
    from repro.core.x64 import x64

    from repro.serving import ReplicaSet

    devs = jax.devices()
    if len(devs) <= 1:
        return {"devices": 1, "skipped": True}

    n = per_batch * 64
    rng = np.random.default_rng(seed)
    cid, qemb, lab = wl.sample_queries(n, rng)
    payloads = np.column_stack([cid, lab])

    def make_set(R, placement):
        return ReplicaSet(
            router, replicas=R, max_batch=per_batch, max_wait_s=0.0005,
            max_inflight=12, coalesce=1, spill_factor=1.0,
            placement=placement,
        )

    # ---- warm every (bucket, device) the timed sections can hit --------
    for R in replicas:
        for placement in ("overlapped", "fused"):
            rset = make_set(R, placement)
            rset.prewarm(budgets=[budget])
            rset.prewarm_compile()
            rset.submit_many(payloads, qemb, budget)
            rset.drain()

    Tp = bucket_size(len(router.engine.arms), 4)
    Bp = int(wave_rows_per_device)
    wrng = np.random.default_rng(seed + 1)
    L = len(router.engine.arms)
    K = router.num_classes

    def wave_args(rows):
        sched = wrng.integers(0, L, size=(Tp, rows)).astype(np.int32)
        resp = wrng.integers(0, K, size=(Tp, rows)).astype(np.int32)
        w = wrng.random((Tp, rows))
        res = np.log(np.maximum(wrng.random((Tp, rows)), 1e-3))
        src = np.broadcast_to(
            np.arange(Tp, dtype=np.int32)[:, None], (Tp, rows)
        ).copy()
        valid = np.ones((Tp, rows), bool)
        empty = np.zeros(rows, np.float64)
        return router_mod._pack_wave(
            sched, resp, w, res, src, valid, empty, router_mod.STOP_MARGIN
        )

    def run_wave(packed_list):
        with router_mod._quiet_donation():
            outs = [
                router_mod._wave_scan(
                    packed, num_classes=K, use_kernel=router.use_kernel,
                )
                for packed in packed_list
            ]
        for o in outs:
            jax.block_until_ready(o)

    wave_staged = {}
    with x64():
        for R in replicas:
            shards = [
                jax.device_put(wave_args(Bp), devs[i % len(devs)])
                for i in range(R)
            ]
            fused = jax.device_put(wave_args(R * Bp), devs[0])
            wave_staged[R] = (shards, fused)
            run_wave(shards)      # warm the per-device shard buckets
            run_wave([fused])     # warm the fused bucket

    sentinel = CompileSentinel({"wave": router_mod._wave_scan})
    sentinel.snapshot()

    # ---- serving rows --------------------------------------------------
    best = {}
    for _ in range(repeats):
        for R in replicas:
            for placement in ("overlapped", "fused"):
                rset = make_set(R, placement)
                t0 = time.perf_counter()
                rset.submit_many(payloads, qemb, budget)
                rset.drain()
                dt = time.perf_counter() - t0
                key = (R, placement)
                if key not in best or dt < best[key][0]:
                    best[key] = (dt, rset)

    rows = []
    for R in replicas:
        dt_o, rset_o = best[(R, "overlapped")]
        dt_f, _ = best[(R, "fused")]
        st = rset_o.stats
        rows.append({
            "replicas": int(R),
            "devices_used": int(st["replica_devices"]),
            "qps_overlapped": n / dt_o,
            "qps_fused": n / dt_f,
            "overlapped_vs_fused": dt_f / dt_o,
            "overlapped_dispatches": int(st["replica_overlapped"]),
        })
        print(
            f"cross-device serving R={R}: overlapped "
            f"{rows[-1]['qps_overlapped']:9.0f} qps vs fused "
            f"{rows[-1]['qps_fused']:9.0f} "
            f"({rows[-1]['overlapped_vs_fused']:4.2f}x) on "
            f"{rows[-1]['devices_used']} device(s)"
        )

    # ---- wave-plane rows -----------------------------------------------
    wave_rows = []
    with x64():
        for R in replicas:
            shards, fused = wave_staged[R]
            t_o = t_f = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                run_wave([fused])
                t_f = min(t_f, time.perf_counter() - t0)
                t0 = time.perf_counter()
                run_wave(shards)
                t_o = min(t_o, time.perf_counter() - t0)
            total = R * Bp
            wave_rows.append({
                "replicas": int(R),
                "rows_total": int(total),
                "qps_overlapped_rows": total / t_o,
                "qps_fused_rows": total / t_f,
                "overlapped_vs_fused": t_f / t_o,
            })
            print(
                f"cross-device wave-plane R={R} ({total} rows): "
                f"overlapped {total / t_o:11.0f} rows/s vs fused "
                f"{total / t_f:11.0f} ({t_f / t_o:4.2f}x)"
            )
    timed_recompiles = sentinel.total()

    # ---- R=1 anchor: overlapped R=1 == plain BatchScheduler ------------
    rset1 = ReplicaSet(make_router(), replicas=1, max_batch=per_batch,
                       max_wait_s=0.0005, max_inflight=12, coalesce=1,
                       placement="overlapped")
    r1_blk = rset1.submit_many(payloads, qemb, budget)
    rset1.drain()
    base = BatchScheduler(make_router(), max_batch=per_batch,
                          max_wait_s=0.0005, max_inflight=12, coalesce=1)
    ref = base.submit_many(payloads, qemb, budget)
    base.drain()
    r1_bitmatch = bool(
        np.array_equal(r1_blk.predictions, ref.predictions)
        and np.array_equal(r1_blk.costs, ref.costs)
        and np.array_equal(r1_blk.stop_waves, ref.stop_waves)
    )

    top = max(replicas)
    by_r = {r["replicas"]: r for r in rows}
    by_wr = {r["replicas"]: r for r in wave_rows}
    cores = os.cpu_count() or 1
    return {
        "devices": len(devs),
        "host_cores": int(cores),
        "parallel_capable": bool(cores >= len(devs)),
        "per_replica_batch": per_batch,
        "queries": n,
        "rows": rows,
        "wave_plane": {
            "rows_per_device": Bp,
            "waves": int(Tp),
            "rows": wave_rows,
        },
        "overlapped_vs_fused_at_max": by_r[top]["overlapped_vs_fused"],
        "wave_overlapped_vs_fused_at_max": by_wr[top]["overlapped_vs_fused"],
        "replicas_max": int(top),
        "r1_bitmatch": r1_bitmatch,
        "timed_recompiles": int(timed_recompiles),
    }


def feedback_drift(num_classes: int, num_arms: int, history: int,
                   chunks: int, chunk: int, seed: int = 29) -> dict:
    """Online-feedback recovery on synthetic drifted traffic.

    Builds a fresh oracle pool over *true* cluster ids (the drift is
    injected into the workload truth, so clustering error is not part of
    this measurement), caches plans, then degrades every arm the served
    plans rely on — for half the clusters — to barely-above-random (0.30 >
    1/K, keeping selection inside the paper's p > 1/K regime). The same
    post-drift stream is routed by the frozen, online and oracle pipelines;
    accuracy is reported over the drifted clusters' tail traffic (the
    second half of the stream, after the online loop has had labels to
    adapt with). Overhead is decomposed: ``steady_overhead_vs_frozen`` is
    the per-chunk cost of carrying the loop when no drift fires (label
    bookkeeping + version checks), ``replan_time_s`` the cold SurGreedy
    selection time the drift chunks paid to re-plan.
    """
    C = 4
    K, L = num_classes, num_arms

    def pool(arm_seed):
        wl = OracleWorkload(num_classes=K, num_clusters=C, num_arms=L, seed=3)
        T, emb, cid_h = wl.response_table(history * C, seed=4)
        est = SuccessProbEstimator(T, emb, cid_h)
        engine = PoolEngine(
            [OracleArm(f"a{i}", wl, i, seed=arm_seed) for i in range(L)]
        )
        return wl, est, engine, ThriftRouter(engine, est, num_classes=K)

    wl, est, engine, router = pool(11)
    wl_f, _, _, frozen_router = pool(13)
    budget = float(np.quantile(engine.costs, 0.5)) * 2
    sched = BatchScheduler(router, max_batch=chunk, max_wait_s=0.0,
                           feedback=True)
    # frozen baseline rides the SAME front-end, just without feedback, so
    # the overhead ratio isolates the loop (labels, folds, version checks,
    # replans) instead of scheduler-vs-bare-engine differences
    frozen = BatchScheduler(frozen_router, max_batch=chunk, max_wait_s=0.0)

    # pre-drift warmup (not timed, not scored): fills the plan caches and
    # compiles the wave program on both pipelines, so `overhead_vs_frozen`
    # measures the feedback loop (labels, folds, drift-gated replans)
    # rather than first-call jit compilation. Replans can deepen plans
    # across wave-depth buckets, so every bucket a replan could land in is
    # compiled up front — warm on any long-running server.
    wrng = np.random.default_rng(seed + 1)
    wcid, wemb, wlab = wl.sample_queries(chunk, wrng)
    wq = np.column_stack([wcid, wlab])
    sched.submit_many(wq, wemb, budget)
    sched.drain()
    frozen.submit_many(wq, wemb, budget)
    frozen.drain()
    router.prewarm_compile(chunk)

    # drift: the served plans' arms degrade for half the clusters
    targets = list(range(C // 2))
    drifted_arms = sorted({
        int(a) for t in targets for a in router.plans.plan(t, budget).order
    })
    for t in targets:
        wl.drift_arms(router.plans.plan(t, budget).order, 0.30, clusters=[t])
    wl_f.p_true[:] = wl.p_true

    # oracle replan: re-estimated from post-drift truth
    T2, emb2, cid2 = wl.response_table(history * C, seed=14)
    oracle = ThriftRouter(
        PoolEngine([OracleArm(f"o{i}", wl, i, seed=12) for i in range(L)]),
        SuccessProbEstimator(T2, emb2, cid2),
        num_classes=K,
    )

    rng = np.random.default_rng(seed)
    stream = [wl.sample_queries(chunk, rng) for _ in range(chunks)]
    accs = {"online": [], "oracle": [], "frozen": []}
    t_online, t_frozen, drift_chunk = [], [], []
    for cid, qemb, lab in stream:
        m = np.isin(cid, targets)
        q = np.column_stack([cid, lab])
        drifts_before = sched.stats["feedback_drifts"]
        t0 = time.perf_counter()
        blk = sched.submit_many(q, qemb, budget)
        sched.drain()
        sched.record_outcomes(blk.request_ids, lab)
        t_online.append(time.perf_counter() - t0)
        drift_chunk.append(sched.stats["feedback_drifts"] > drifts_before)
        t0 = time.perf_counter()
        fblk = frozen.submit_many(q, qemb, budget)
        frozen.drain()
        t_frozen.append(time.perf_counter() - t0)
        ores = oracle.route_batch(q, qemb, budget)
        accs["online"].append(float((blk.predictions[m] == lab[m]).mean()))
        accs["oracle"].append(float((ores.predictions[m] == lab[m]).mean()))
        accs["frozen"].append(float((fblk.predictions[m] == lab[m]).mean()))

    tail = chunks // 2
    online, oracle_acc, frozen_acc = (
        float(np.mean(accs[k][tail:])) for k in ("online", "oracle", "frozen")
    )
    st = dict(sched.stats)
    # overhead decomposition: drift chunks pay cold SurGreedy selection for
    # the re-planned clusters (the cost the plan cache amortizes everywhere
    # else); steady chunks pay only label bookkeeping + version checks
    steady_online = [t for t, d in zip(t_online, drift_chunk) if not d]
    steady_ratio = (
        float(np.median(steady_online) / np.median(t_frozen))
        if steady_online else float("nan")
    )
    replan_s = max(0.0, float(
        sum(t for t, d in zip(t_online, drift_chunk) if d)
        - (np.median(steady_online) if steady_online else 0.0) * sum(drift_chunk)
    ))
    return {
        "chunks": chunks,
        "chunk": chunk,
        "drifted_clusters": targets,
        "drifted_arms": drifted_arms,
        "online_acc": online,
        "oracle_acc": oracle_acc,
        "frozen_acc": frozen_acc,
        "recovery": online / max(oracle_acc, 1e-12),
        "frozen_vs_oracle": frozen_acc / max(oracle_acc, 1e-12),
        "acc_trajectory": {k: [round(a, 4) for a in v] for k, v in accs.items()},
        "overhead_vs_frozen": float(sum(t_online) / max(sum(t_frozen), 1e-12)),
        "steady_overhead_vs_frozen": steady_ratio,
        "replan_time_s": replan_s,
        "drift_chunks": int(sum(drift_chunk)),
        "feedback_labels": int(st["feedback_labels"]),
        "feedback_applies": int(st["feedback_applies"]),
        "feedback_drifts": int(st["feedback_drifts"]),
        "plan_stale_dropped": int(st["plan_stale_dropped"]),
        "plan_batch_replans": int(st["plan_batch_replans"]),
        "plan_batch_replanned": int(st["plan_batch_replanned"]),
        "plan_misses": int(st["plan_misses"]),
        "estimator_version": int(est.version),
        "estimator_plan_version": int(est.plan_version),
    }


def fault_tolerance(num_classes: int, num_arms: int, history: int,
                    chunks: int, chunk: int, seed: int = 37) -> dict:
    """Accuracy + tail latency under an injected 2-arm outage.

    The two arms the cached plans lean on hardest (the wave-0/1 heads) go
    fully down (error rate 1.0). The same post-outage stream is served by
    three pipelines plus a no-fault baseline:

      * ``frozen``   — failover off, no feedback: failed waves simply
        vanish from every belief (the pre-hardening behavior);
      * ``failover`` — in-wave failover re-routes each failed slot to the
        plan's next-best affordable arm inside the compiled wave program;
      * ``replan``   — failover + the degradation tracker: failure
        evidence folds into the estimator, the Wilson drift gate replans
        the outage away, probes stand by to readmit.

    The acceptance bar (full run): ``replan`` recovers >= 80% of the
    no-fault accuracy while ``frozen`` does not.
    """
    C = 4
    K, L = num_classes, num_arms

    def pool(failover=True):
        wl = OracleWorkload(num_classes=K, num_clusters=C, num_arms=L, seed=3)
        T, emb, cid_h = wl.response_table(history * C, seed=4)
        est = SuccessProbEstimator(T, emb, cid_h)
        engine = PoolEngine(
            [OracleArm(f"a{i}", wl, i, seed=11) for i in range(L)]
        )
        router = ThriftRouter(engine, est, num_classes=K, failover=failover)
        return wl, engine, router

    wl, engine_b, baseline_r = pool()
    _, engine_z, frozen_r = pool(failover=False)
    _, engine_f, failover_r = pool()
    _, engine_p, replan_r = pool()
    # tight budget -> shallow plans: an outage of the workhorse arms leaves
    # no slack inside the frozen plan, so only replanning can recover
    budget = float(np.quantile(engine_b.costs, 0.45)) * 1.3

    scheds = {
        "baseline": BatchScheduler(baseline_r, max_batch=chunk, max_wait_s=0.0),
        "frozen": BatchScheduler(frozen_r, max_batch=chunk, max_wait_s=0.0),
        "failover": BatchScheduler(failover_r, max_batch=chunk, max_wait_s=0.0),
        "replan": BatchScheduler(replan_r, max_batch=chunk, max_wait_s=0.0,
                                 feedback=True),
    }
    # warmup (not scored): plan caches + wave-program buckets on every plane
    wrng = np.random.default_rng(seed + 1)
    wcid, wemb, wlab = wl.sample_queries(chunk, wrng)
    wq = np.column_stack([wcid, wlab])
    for s in scheds.values():
        s.submit_many(wq, wemb, budget)
        s.drain()

    # the outage: kill the two arms the served plans invoke most
    res = baseline_r.route_batch(wq, wemb, budget)
    flat = res.schedule[res.invoked]
    counts = np.bincount(flat, minlength=L)
    dead = np.argsort(-counts)[:2].tolist()
    for engine in (engine_z, engine_f, engine_p):
        engine.fault_policy = FaultPolicy(L, K, seed=seed).set_arms(
            dead, error=1.0
        )

    rng = np.random.default_rng(seed)
    accs = {name: [] for name in scheds}
    for cid, qemb, lab in [wl.sample_queries(chunk, rng) for _ in range(chunks)]:
        q = np.column_stack([cid, lab])
        for name, sched in scheds.items():
            blk = sched.submit_many(q, qemb, budget)
            sched.drain()
            accs[name].append(float((blk.predictions == lab).mean()))
            for e in (engine_z, engine_f, engine_p):
                if e.fault_policy is not None:
                    e.fault_policy.advance()

    tail = chunks // 2
    mean_acc = {k: float(np.mean(v[tail:])) for k, v in accs.items()}
    base = max(mean_acc["baseline"], 1e-12)
    st = dict(scheds["replan"].stats)
    out = {
        "chunks": chunks,
        "chunk": chunk,
        "dead_arms": dead,
        "baseline_acc": mean_acc["baseline"],
        "frozen_acc": mean_acc["frozen"],
        "failover_acc": mean_acc["failover"],
        "replan_acc": mean_acc["replan"],
        "frozen_recovery": mean_acc["frozen"] / base,
        "failover_recovery": mean_acc["failover"] / base,
        "replan_recovery": mean_acc["replan"] / base,
        "acc_trajectory": {k: [round(a, 4) for a in v] for k, v in accs.items()},
        "p99_ms": {
            name: float(s.latency_stats().get("p99_s", 0.0)) * 1e3
            for name, s in scheds.items()
        },
        "degradation_failures": int(st.get("degradation_failures", 0)),
        "feedback_drifts": int(st.get("feedback_drifts", 0)),
        "plan_stale_dropped": int(st.get("plan_stale_dropped", 0)),
    }
    return out


def selection_replan(num_arms: int, classes: int, history: int,
                     groups=(1, 8, 64), repeats: int = 3, seed: int = 31,
                     eps: float = 0.25) -> dict:
    """Serial vs batched drift-replan latency at G drifted clusters.

    The PR 5 tentpole measurement: a pool with ``max(groups)`` clusters is
    fully planned, then G clusters' estimates are invalidated
    (``estimator.touch``) and the dropped plans re-select — once through
    the serial per-pair path (``PlanService(batched=False)``: one SurGreedy
    host loop per cluster, a device dispatch per greedy round per group)
    and once through the batched planner (one ``select_many`` program for
    all G). Both paths are warmed first (plan build + one replan cycle, so
    jit compilation is excluded on both sides), the selector memo is
    cleared before every timed replan (a replan must re-select, not re-hit
    the memo), and rounds interleave serial/batched so shared-host noise
    penalizes both equally. ``eps`` sizes the Monte-Carlo budget the way a
    serving replan would (theta ~ 1/eps^2).
    """
    C = int(max(groups))
    K, L = classes, num_arms
    wl = OracleWorkload(num_classes=K, num_clusters=C, num_arms=L, seed=7)
    T, emb, cid_h = wl.response_table(history * C, seed=8)

    def mk(batched: bool):
        est = SuccessProbEstimator(T, emb, cid_h)
        engine = PoolEngine(
            [OracleArm(f"b{i}", wl, i, seed=21) for i in range(L)]
        )
        router = ThriftRouter(engine, est, num_classes=K, eps=eps)
        router.plans.batched = batched
        return est, router

    est_s, router_s = mk(False)
    est_b, router_b = mk(True)
    budget = float(np.quantile(router_s.engine.costs, 0.6)) * 2

    def replan_once(router, est, cids):
        for c in cids:
            est.touch(int(c))
        router.selector._cache.clear()   # a replan re-selects, never memo-hits
        t0 = time.perf_counter()
        n = router.plans.replan_stale()
        return time.perf_counter() - t0, n

    rows = []
    plans_match = True
    for G in groups:
        sides = [(router_s, est_s), (router_b, est_b)]
        cid_sets = [
            [int(c) for c in est.cluster_order[:G]] for _, est in sides
        ]
        for (router, est), cids in zip(sides, cid_sets):
            router.plans.plan_many([(c, budget) for c in cids])  # cold build
            replan_once(router, est, cids)                       # warm compile
        best = [np.inf, np.inf]
        rebuilt = [0, 0]
        for _ in range(repeats):
            for i, ((router, est), cids) in enumerate(zip(sides, cid_sets)):
                dt, n = replan_once(router, est, cids)
                best[i] = min(best[i], dt)
                rebuilt[i] = n
        for c_s, c_b in zip(*cid_sets):
            p_s = router_s.plans.plan(c_s, budget)
            p_b = router_b.plans.plan(c_b, budget)
            plans_match &= bool(np.array_equal(p_s.order, p_b.order))
        row = {
            "groups": int(G),
            "serial_s": best[0],
            "batched_s": best[1],
            "speedup": best[0] / best[1],
            "replanned_serial": int(rebuilt[0]),
            "replanned_batched": int(rebuilt[1]),
        }
        rows.append(row)
        print(
            f"selection replan G={G:3d}: serial {1e3 * row['serial_s']:8.1f}ms"
            f" | batched {1e3 * row['batched_s']:8.1f}ms"
            f" | {row['speedup']:5.2f}x ({row['replanned_batched']} plans)"
        )
    return {
        "rows": rows,
        "pool": {"arms": L, "classes": K, "clusters": C, "budget": budget},
        "eps": eps,
        "groups_max": int(max(groups)),
        "speedup_at_max": rows[-1]["speedup"],
        "plans_match": plans_match,
    }


# ---------------------------------------------------------------------------
# raw_speed: the PR 10 section — fully on-device planner vs the PR 9
# host-gamma plane and donation on/off wave-loop timings.
# ---------------------------------------------------------------------------


def _same_plan(a, b) -> bool:
    """Bitwise equality of two SelectionResults (everything derived)."""
    if not np.array_equal(a.chosen, b.chosen):
        return False
    if not (a.xi_est == b.xi_est and a.cost == b.cost):
        return False
    if (a.s1 is None) != (b.s1 is None):
        return False
    if a.s1 is not None:
        return bool(
            np.array_equal(a.s1, b.s1) and np.array_equal(a.s2, b.s2)
            and a.l_star == b.l_star and a.xi_s1 == b.xi_s1
            and a.xi_s2 == b.xi_s2
        )
    return True


def raw_speed(num_arms: int, classes: int, groups=(1, 8, 64),
              repeats: int = 5, wave_batch: int = 256,
              wave_repeats: int = 10, seed: int = 47) -> dict:
    """The PR 10 measurements, two blocks:

    * ``planner`` — the fully on-device plane (``sur_greedy_many``: greedy-
      on-gamma, l*, and candidate scoring fused into the scan program) vs
      the retained PR 9 plane (``_sur_greedy_many_hostgamma``: device xi
      greedy + per-group host loop + separate final-xi dispatch) at G
      drifted groups, bit-identical plans asserted per pair;
    * ``donation`` — the serving wave loop with donated staged tables
      (``donate_buffers=True``, the default) vs the nodonate twin, outputs
      bit-checked (donation is a storage contract, not a numerics knob; on
      backends where the reduction outputs can't alias the staged tables
      the timing delta is expected to be noise).

    All timed loops run strictly after per-bucket warm-ups; a local
    CompileSentinel records ``timed_recompiles`` for the section.
    """
    from repro.core.selection import _sur_greedy_many_hostgamma, sur_greedy_many

    K, L = classes, num_arms
    rng = np.random.default_rng(seed)
    b = rng.uniform(0.05, 1.0, L)
    key = jax.random.key(9)
    theta = 200                      # pins one theta bucket for every G

    sentinel = CompileSentinel({
        "plan": selection_mod._sur_greedy_scan,
        "plan_nodonate": selection_mod._sur_greedy_scan_nodonate,
        "wave": router_mod._wave_scan,
        "wave_nodonate": router_mod._wave_scan_nodonate,
    })

    cases = {}
    for G in groups:
        ps = rng.uniform(0.2, 0.98, (G, L))
        budgets = rng.uniform(0.4, 2.5, G)
        thetas = np.full(G, theta)
        cases[G] = (ps, budgets, thetas)
        # warm both planes' (G-bucket, L, theta-bucket, K) programs
        sur_greedy_many(ps, b, budgets, K, key, thetas)
        _sur_greedy_many_hostgamma(ps, b, budgets, K, key, thetas)

    sentinel.snapshot()          # planner warm-ups done: timed loops start
    plan_rows = []
    plans_match = True
    for G in groups:
        ps, budgets, thetas = cases[G]
        t_host, t_fused = _time_all(
            [
                lambda: _sur_greedy_many_hostgamma(
                    ps, b, budgets, K, key, thetas
                ),
                lambda: sur_greedy_many(ps, b, budgets, K, key, thetas),
            ],
            repeats,
        )
        fused = sur_greedy_many(ps, b, budgets, K, key, thetas)
        host = _sur_greedy_many_hostgamma(ps, b, budgets, K, key, thetas)
        for f_r, h_r in zip(fused, host):
            plans_match &= _same_plan(f_r, h_r)
        row = {
            "groups": int(G),
            "hostgamma_s": t_host,
            "fused_s": t_fused,
            "speedup": t_host / t_fused,
        }
        plan_rows.append(row)
        print(
            f"raw speed planner G={G:3d}: hostgamma "
            f"{1e3 * t_host:7.1f}ms | fused {1e3 * t_fused:7.1f}ms | "
            f"{row['speedup']:5.2f}x"
        )
    timed_recompiles = sentinel.total()

    # -- donation on/off wave-loop timings -------------------------------
    wl = OracleWorkload(
        num_classes=K, num_clusters=5, num_arms=L, seed=seed + 1
    )
    T, emb, cid_h = wl.response_table(60 * 5, seed=seed + 2)
    assign, _ = kmeans(emb, 5, seed=0)
    est = SuccessProbEstimator(T, emb, assign)

    def mk(donate: bool):
        engine = PoolEngine(
            [OracleArm(f"d{i}", wl, i, seed=33) for i in range(L)]
        )
        return ThriftRouter(
            engine, est, num_classes=K, donate_buffers=donate
        )

    router_d, router_nd = mk(True), mk(False)
    budget = float(np.quantile(router_d.engine.costs, 0.6)) * 2
    qrng = np.random.default_rng(seed + 3)
    cid, qemb, lab = wl.sample_queries(wave_batch, qrng)
    queries = np.column_stack([cid, lab])
    res_d = router_d.route_batch(queries, qemb, budget)     # warm + result
    res_nd = router_nd.route_batch(queries, qemb, budget)   # (nodonate twin
    # owns a separate jit cache: this warm-up is its first-ever compile)
    donation_match = bool(
        np.array_equal(res_d.predictions, res_nd.predictions)
        and np.array_equal(res_d.costs, res_nd.costs)
        and np.array_equal(res_d.planned_costs, res_nd.planned_costs)
        and res_d.arms_used == res_nd.arms_used
    )
    sentinel.snapshot()          # donation warm-ups done: timed loop starts
    t_d, t_nd = _time_all(
        [
            lambda: router_d.route_batch(queries, qemb, budget),
            lambda: router_nd.route_batch(queries, qemb, budget),
        ],
        wave_repeats,
    )
    donation = {
        "batch": int(wave_batch),
        "donate_s": t_d,
        "nodonate_s": t_nd,
        "nodonate_over_donate": t_nd / t_d,
        "bit_identical": donation_match,
    }
    print(
        f"raw speed donation B={wave_batch}: donate {1e3 * t_d:7.2f}ms | "
        f"nodonate {1e3 * t_nd:7.2f}ms ({donation['nodonate_over_donate']:.2f}x)"
        f" | bit-identical {donation_match}"
    )
    timed_recompiles += sentinel.total()

    return {
        "planner": {
            "rows": plan_rows,
            "groups_max": int(max(groups)),
            "speedup_at_max": plan_rows[-1]["speedup"],
            "plans_match": plans_match,
            "theta": theta,
        },
        "donation": donation,
        "timed_recompiles": int(timed_recompiles),
    }


def _time_all(fns, repeats: int):
    """Best-of-``repeats`` wall time per engine, *interleaved* round-robin
    so a load spike on the shared host penalizes every engine equally
    instead of whichever happened to be mid-measurement."""
    best = [np.inf] * len(fns)
    for _ in range(repeats):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def run(args) -> dict:
    wl = OracleWorkload(
        num_classes=args.classes, num_clusters=args.clusters, num_arms=args.arms, seed=3
    )
    T, emb, _ = wl.response_table(args.history)
    assign, _ = kmeans(emb, args.clusters, seed=0)
    est = SuccessProbEstimator(T, emb, assign)

    engine = PoolEngine([OracleArm(f"a{i}", wl, i, seed=11) for i in range(args.arms)])
    seed_engine = PoolEngine(
        [_SeedOracleArm(f"s{i}", wl, i, seed=11) for i in range(args.arms)]
    )
    router = ThriftRouter(engine, est, num_classes=args.classes)
    budget = float(np.quantile(engine.costs, 0.7)) * 2

    batches = args.batches or BATCH_SIZES
    rows = []
    rng = np.random.default_rng(17)
    # thriftlint's runtime half: count actual XLA compilations of the wave
    # program and the batched planner across the whole bench, and demand
    # that the *timed* sections never compile (all compiles live in the
    # per-bucket warm-ups).
    sentinel = CompileSentinel(
        {"wave": router_mod._wave_scan, "plan": selection_mod._sur_greedy_scan}
    )
    timed_recompiles = 0
    for B in batches:
        cid, qemb, lab = wl.sample_queries(B, rng)
        # (B, 2) payload array: what a serving front-end hands the engine
        # (same input to all three engines; avoids per-call list conversion)
        queries = np.column_stack([cid, lab])
        # warm-up: populates the plan/selection caches and compiles the
        # jitted wave loop for this (B, T) bucket, for all three engines
        res = router.route_batch(queries, qemb, budget)
        router.route_batch_reference(queries, qemb, budget)
        seed_route_batch(router, seed_engine, queries, qemb, budget)

        # the interesting scaling story lives at the big batches — sample
        # them harder so best-of converges despite shared-host noise
        sentinel.snapshot()          # warm-ups done: timed runs must not compile
        reps = args.repeats * (3 if B >= 512 else 1)
        t_jit, t_wave = _time_all(
            [
                lambda: router.route_batch(queries, qemb, budget),
                lambda: router.route_batch_reference(queries, qemb, budget),
            ],
            reps,
        )
        (t_seed,) = _time_all(
            [lambda: seed_route_batch(router, seed_engine, queries, qemb, budget)],
            max(1, args.repeats // 2),
        )
        res = router.route_batch(queries, qemb, budget)
        row = {
            "batch": B,
            "qps": B / t_jit,                       # jitted engine (route_batch)
            "wavefront_qps": B / t_wave,            # PR 1 compacting wavefront
            "seed_qps": B / t_seed,
            "speedup": t_seed / t_jit,              # jit vs seed
            "jit_over_wavefront": t_wave / t_jit,   # PR 2 vs PR 1
            "waves": int(res.waves),
            "mean_realized_cost": float(res.costs.mean()),
            "mean_planned_cost": float(res.planned_costs.mean()),
            "realized_over_planned": float(res.costs.sum() / res.planned_costs.sum()),
            "accuracy": float((res.predictions == lab).mean()),
        }
        timed_recompiles += sentinel.total()
        rows.append(row)
        print(
            f"batch {B:5d}: jit {row['qps']:9.0f} qps | wavefront "
            f"{row['wavefront_qps']:9.0f} ({row['jit_over_wavefront']:4.2f}x) | "
            f"seed {row['seed_qps']:8.0f} ({row['speedup']:4.1f}x) | "
            f"realized/planned {row['realized_over_planned']:.3f} | "
            f"acc {row['accuracy']:.3f}"
        )

    # continuous-batching front-end under a steady-state arrival process
    steady = steady_state(
        router, wl, budget, batch=args.steady_batch,
        n_queries=args.steady_queries or 8 * args.steady_batch,
        load=args.load,
    )
    print(
        f"steady-state (max_batch {steady['max_batch']}): saturated "
        f"{steady['saturated_qps']:9.0f} qps "
        f"({steady['vs_jit_engine']:4.2f}x one-shot jit, paired)"
        f" | offered {steady['offered_qps']:9.0f} -> {steady['steady_qps']:9.0f} qps"
        f" | p50 {steady['p50_ms']:.2f}ms p99 {steady['p99_ms']:.2f}ms"
        f" | planes jit={steady['spec_jit']} ref={steady['spec_reference']}"
    )

    # R-replica serving plane: qps/p99 vs R at fixed per-replica batch
    def make_router():
        eng = PoolEngine(
            [OracleArm(f"r{i}", wl, i, seed=61) for i in range(args.arms)]
        )
        return ThriftRouter(eng, est, num_classes=args.classes)

    replica = replica_scaling(
        router, wl, budget, per_batch=args.replica_batch,
        make_router=make_router,
        repeats=max(2 if args.smoke else 6, args.repeats // 4),
    )
    print(
        f"replica scaling: {replica['speedup_at_max']:.2f}x aggregate qps at "
        f"R={replica['replicas_max']} (per-replica batch "
        f"{replica['per_replica_batch']}) | R=1 bit-matches steady path: "
        f"{replica['r1_bitmatch_steady']} | timed recompiles "
        f"{replica['timed_recompiles']}"
    )

    # cross-device placement curve (overlapped-R-devices vs fused-1-device)
    replica["cross_device"] = cross_device(
        router, wl, budget, per_batch=args.replica_batch,
        make_router=make_router,
        repeats=2 if args.smoke else max(3, args.repeats // 8),
        wave_rows_per_device=1024 if args.smoke else 4096,
    )
    cd = replica["cross_device"]
    if cd.get("skipped"):
        print("cross-device: skipped (single-device process — run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    else:
        print(
            f"cross-device: serving {cd['overlapped_vs_fused_at_max']:.2f}x, "
            f"wave-plane {cd['wave_overlapped_vs_fused_at_max']:.2f}x "
            f"overlapped-vs-fused at R={cd['replicas_max']} on "
            f"{cd['devices']} device(s) / {cd['host_cores']} core(s) "
            f"(parallel-capable: {cd['parallel_capable']}) | R=1 bit-match "
            f"{cd['r1_bitmatch']} | timed recompiles {cd['timed_recompiles']}"
        )

    # batched planner: serial vs batched drift-replan latency
    selection = selection_replan(
        args.arms, args.classes, history=args.selection_history,
        repeats=args.selection_repeats,
    )
    print(
        f"selection replan: {selection['speedup_at_max']:.2f}x batched over "
        f"serial at G={selection['groups_max']} drifted clusters "
        f"(plans match: {selection['plans_match']})"
    )

    # raw-speed pass: fused on-device planner vs PR 9 host-gamma plane,
    # donated vs non-donated wave dispatch
    raw = raw_speed(
        args.arms, args.classes,
        repeats=args.raw_repeats,
        wave_batch=min(256, max(batches)),
        wave_repeats=max(4, args.repeats // 2),
    )
    print(
        f"raw speed: planner {raw['planner']['speedup_at_max']:.2f}x fused "
        f"over hostgamma at G={raw['planner']['groups_max']} (plans match: "
        f"{raw['planner']['plans_match']}) | donation bit-identical "
        f"{raw['donation']['bit_identical']} | timed recompiles "
        f"{raw['timed_recompiles']}"
    )

    # online estimation feedback on drifted traffic
    feedback = feedback_drift(
        args.classes, args.arms, history=args.feedback_history,
        chunks=args.feedback_chunks, chunk=args.feedback_chunk,
    )
    print(
        f"feedback (drifted traffic): online {feedback['online_acc']:.3f} "
        f"vs oracle {feedback['oracle_acc']:.3f} "
        f"({feedback['recovery']:.2f} recovery) vs frozen "
        f"{feedback['frozen_acc']:.3f} ({feedback['frozen_vs_oracle']:.2f})"
        f" | drifts {feedback['feedback_drifts']} replans "
        f"{feedback['plan_stale_dropped']} | steady overhead "
        f"{feedback['steady_overhead_vs_frozen']:.2f}x frozen, replans "
        f"{feedback['replan_time_s']:.2f}s over {feedback['drift_chunks']} chunks"
    )

    # failure plane: accuracy + p99 under an injected 2-arm outage
    fault = fault_tolerance(
        args.classes, args.arms, history=args.feedback_history,
        chunks=args.feedback_chunks, chunk=args.feedback_chunk,
    )
    print(
        f"fault tolerance (2-arm outage {fault['dead_arms']}): baseline "
        f"{fault['baseline_acc']:.3f} | frozen {fault['frozen_acc']:.3f} "
        f"({fault['frozen_recovery']:.2f}) | failover "
        f"{fault['failover_acc']:.3f} ({fault['failover_recovery']:.2f}) | "
        f"failover+replan {fault['replan_acc']:.3f} "
        f"({fault['replan_recovery']:.2f}) | failures folded "
        f"{fault['degradation_failures']}, drifts {fault['feedback_drifts']}"
    )

    # compile-bucket budgets: every wave program is keyed by a (B, T)
    # bucket pair and every planner program by a (G, theta) bucket pair, so
    # the whole bench — including the continuous-batching steady state and
    # every drift replan — may compile at most |buckets| programs, and the
    # timed row sections exactly zero.
    wave_b = {bucket_size(n, 8) for n in range(1, max(
        list(batches) + [args.steady_batch, 4 * args.replica_batch]) + 1)}
    cd = replica.get("cross_device", {})
    wp = cd.get("wave_plane")
    if wp:   # cross-device wave-plane shapes join the bucket census
        wave_b.add(bucket_size(wp["rows_per_device"], 8))
        for r in wp["rows"]:
            wave_b.add(bucket_size(r["rows_total"], 8))
    wave_t = {bucket_size(t, 4) for t in range(1, args.arms + 1)}
    plan_g = {bucket_size(g, 8) for g in range(1, 129)}
    plan_theta = {bucket_size(t, 4) for t in range(1, 4097)}
    # the jit cache keys executables by (bucket, device): a multi-device
    # process may legitimately hold one copy of a bucket program per device
    n_devices = max(1, int(cd.get("devices", 1)))
    timed_recompiles += raw["timed_recompiles"]   # raw_speed's own sentinel
    compile_sentinel = {
        "timed_recompiles": timed_recompiles,
        "wave_compiles": compile_cache_size(sentinel.entries["wave"]),
        "wave_bucket_budget": len(wave_b) * len(wave_t) * n_devices,
        "plan_compiles": compile_cache_size(sentinel.entries["plan"]),
        "plan_bucket_budget": len(plan_g) * len(plan_theta),
    }
    compile_sentinel["within_budget"] = bool(
        timed_recompiles == 0
        and compile_sentinel["wave_compiles"]
        <= compile_sentinel["wave_bucket_budget"]
        and compile_sentinel["plan_compiles"]
        <= compile_sentinel["plan_bucket_budget"]
    )
    print(
        f"compile sentinel: wave {compile_sentinel['wave_compiles']}"
        f"/{compile_sentinel['wave_bucket_budget']} bucket programs, plan "
        f"{compile_sentinel['plan_compiles']}"
        f"/{compile_sentinel['plan_bucket_budget']}, timed-section "
        f"recompiles {timed_recompiles} (budget holds: "
        f"{compile_sentinel['within_budget']})"
    )

    report = {
        "bench": "serving_throughput",
        "engine": "continuous-batching",
        "pool": {
            "arms": args.arms,
            "classes": args.classes,
            "clusters": args.clusters,
            "budget": budget,
        },
        "rows": rows,
        "steady_state": steady,
        "replica_scaling": replica,
        "selection": selection,
        "raw_speed": raw,
        "feedback": feedback,
        "fault_tolerance": fault,
        "compile_sentinel": compile_sentinel,
        "plan_cache": router.plans.stats(),
        "history": _load_history(args.out),
    }
    for key, field in (
        ("speedup_at_256", "speedup"),
        ("jit_over_wavefront_at_1024", "jit_over_wavefront"),
    ):
        vals = [r[field] for r in rows if r["batch"] == int(key.rsplit("_", 1)[1])]
        if vals:
            report[key] = vals[0]
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    msg = ", ".join(
        f"{k} = {report[k]:.1f}x"
        for k in ("speedup_at_256", "jit_over_wavefront_at_1024")
        if k in report
    )
    print(f"wrote {args.out} ({msg})" if msg else f"wrote {args.out}")
    return report


def _load_history(path: str) -> list:
    """Earlier reports at ``path`` become compact history entries (summary
    scalars + per-batch qps, not full rows), so the file keeps the whole
    seed -> wavefront -> jitted trajectory across PRs without ballooning."""
    try:
        with open(path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        return []
    history = prev.get("history", [])
    entry = {
        "engine": prev.get("engine", "wavefront"),   # pre-PR2 reports
        "pool": prev.get("pool"),
        "qps": {str(r["batch"]): r["qps"] for r in prev.get("rows", []) if "qps" in r},
    }
    for key in ("speedup_at_256", "jit_over_wavefront_at_1024"):
        if key in prev:
            entry[key] = prev[key]
    steady = prev.get("steady_state")
    if steady:
        entry["steady_state"] = {
            k: steady[k]
            for k in ("max_batch", "saturated_qps", "steady_qps",
                      "p50_ms", "p99_ms", "vs_jit_engine")
            if k in steady
        }
    replica = prev.get("replica_scaling")
    if replica:
        entry["replica_scaling"] = {
            k: replica[k]
            for k in ("per_replica_batch", "replicas_max", "speedup_at_max",
                      "r1_bitmatch_steady")
            if k in replica
        }
        entry["replica_scaling"]["qps"] = {
            str(r["replicas"]): r["qps"] for r in replica.get("rows", [])
        }
        cd = replica.get("cross_device")
        if cd and not cd.get("skipped"):
            entry["replica_scaling"]["cross_device"] = {
                k: cd[k]
                for k in ("devices", "host_cores", "parallel_capable",
                          "overlapped_vs_fused_at_max",
                          "wave_overlapped_vs_fused_at_max", "r1_bitmatch")
                if k in cd
            }
    feedback = prev.get("feedback")
    if feedback:
        entry["feedback"] = {
            k: feedback[k]
            for k in ("online_acc", "oracle_acc", "frozen_acc", "recovery",
                      "overhead_vs_frozen")
            if k in feedback
        }
    selection = prev.get("selection")
    if selection:
        entry["selection"] = {
            k: selection[k]
            for k in ("groups_max", "speedup_at_max", "plans_match")
            if k in selection
        }
    raw = prev.get("raw_speed")
    if raw:
        planner = raw.get("planner", {})
        entry["raw_speed"] = {
            k: planner[k]
            for k in ("groups_max", "speedup_at_max", "plans_match")
            if k in planner
        }
        donation = raw.get("donation", {})
        if donation:
            entry["raw_speed"]["donation_bit_identical"] = donation.get(
                "bit_identical"
            )
            entry["raw_speed"]["nodonate_over_donate"] = donation.get(
                "nodonate_over_donate"
            )
    fault = prev.get("fault_tolerance")
    if fault:
        entry["fault_tolerance"] = {
            k: fault[k]
            for k in ("baseline_acc", "frozen_recovery", "failover_recovery",
                      "replan_recovery", "dead_arms")
            if k in fault
        }
    history.append(entry)
    return history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arms", type=int, default=12)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--clusters", type=int, default=6)
    ap.add_argument("--history", type=int, default=2000)
    ap.add_argument("--repeats", type=int, default=25)
    ap.add_argument("--batches", type=int, nargs="*", default=None)
    ap.add_argument(
        "--steady-batch", type=int, default=256,
        help="admission batch size of the steady-state front-end run",
    )
    ap.add_argument(
        "--steady-queries", type=int, default=None,
        help="request-stream length for the steady-state run (default 8x batch)",
    )
    ap.add_argument(
        "--replica-batch", type=int, default=24,
        help="fixed per-replica admission batch for the replica_scaling sweep",
    )
    ap.add_argument(
        "--load", type=float, default=0.7,
        help="steady-state offered load as a fraction of measured capacity",
    )
    ap.add_argument(
        "--feedback-chunks", type=int, default=8,
        help="drifted-traffic chunks streamed through the feedback loop",
    )
    ap.add_argument(
        "--feedback-chunk", type=int, default=256,
        help="requests per drifted-traffic chunk",
    )
    ap.add_argument(
        "--feedback-history", type=int, default=120,
        help="historical responses per cluster for the feedback scenario",
    )
    ap.add_argument(
        "--selection-history", type=int, default=120,
        help="historical responses per cluster for the replan scenario",
    )
    ap.add_argument(
        "--selection-repeats", type=int, default=3,
        help="best-of rounds for the serial-vs-batched replan timing",
    )
    ap.add_argument(
        "--raw-repeats", type=int, default=5,
        help="best-of rounds for the raw-speed planner timings",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny sweep for CI: small batches, few repeats",
    )
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    if args.smoke:
        args.batches = args.batches or [32, 64]
        args.repeats = min(args.repeats, 2)
        args.history = min(args.history, 600)
        args.steady_batch = min(args.steady_batch, 64)
        args.steady_queries = args.steady_queries or 4 * args.steady_batch
        args.replica_batch = min(args.replica_batch, 32)
        args.feedback_chunks = min(args.feedback_chunks, 6)
        args.feedback_chunk = min(args.feedback_chunk, 128)
        args.feedback_history = min(args.feedback_history, 80)
        args.selection_history = min(args.selection_history, 60)
        args.selection_repeats = min(args.selection_repeats, 2)
        args.raw_repeats = min(args.raw_repeats, 2)
    configure_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
