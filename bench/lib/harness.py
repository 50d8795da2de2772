"""Drive the program under test through one cell of the benchmark.

This is the only module of the benchmark that imports the program. It
builds the deployment a configuration names (pool, calibration, router,
scheduler), serves a traffic mix through the program's own entry points
(``submit_many`` / ``pump`` / ``drain``), records what the comparison with
the reference needs, and, in a traced run, wraps the program's methods in
``jax.profiler.TraceAnnotation`` spans named ``bench.*``. It never changes
what the program computes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
import types
from typing import Dict, List, Optional

import numpy as np

from . import check, fold, planref, reference, stats, traffic as traffic_mod
from .peaks import peaks
from .pool import Pool

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


# ---------------------------------------------------------------------------
# The benchmark's data, found by name
# ---------------------------------------------------------------------------

def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: Optional[dict] = None):
    """``(cell, config, mix)`` of the workload called ``name``."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = traffic_mod.load_mix(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell, config, mix


def metric_reader(name: str):
    """The reader of a per-layer metric: ``metrics/<name>.py``, or the file
    of its base name (before the first ``.``) that serves every variant."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def cell_metrics(bench: dict, cell_name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end":
            out.append(m)
        else:
            moved = e2e[m["moves"]]
            if "workloads" not in moved or cell_name in moved["workloads"]:
                out.append(m)
    return out


# ---------------------------------------------------------------------------
# JAX, the chip and compile counting
# ---------------------------------------------------------------------------

class CompileCount:
    """Programs obtained by the process (backend compiles plus loads from
    the persistent cache), counted through ``jax.monitoring``."""

    _installed: Optional["CompileCount"] = None

    def __init__(self):
        self.n = 0

    @classmethod
    def get(cls) -> "CompileCount":
        if cls._installed is None:
            import jax

            counter = cls()

            def on_duration(event, duration, **kw):
                if event == "/jax/core/compile/backend_compile_duration":
                    counter.n += 1

            def on_event(event, **kw):
                if event == "/jax/compilation_cache/cache_hits":
                    counter.n += 1

            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
            cls._installed = counter
        return cls._installed


def start_jax(chips: int, require_tpu: bool = True):
    """Import JAX, demand the chips, and keep compiled programs in the
    checkout's ``.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` says
    otherwise. Returns the devices the cell uses."""
    import os

    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devices[0].platform}")
        if len(devices) < chips:
            raise NoChip(f"{len(devices)} chips, the cell needs {chips}")
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devices[:chips]


# ---------------------------------------------------------------------------
# The deployment
# ---------------------------------------------------------------------------

def _answers_engine():
    from repro.serving import PoolEngine

    class FixedAnswers(PoolEngine):
        """Each arm's answer to each query is read from a table drawn up
        front, so every plane (and the reference) sees the same answers."""

        answers: np.ndarray = None     # (L, N) set per traffic

        def invoke_grid(self, sched_T, payloads):
            return self.answers[np.maximum(sched_T, 0), payloads[None, :, 2]]

        def invoke_rows(self, arm_ids, queries, rows):
            q = np.asarray(queries, np.int64)
            return self.answers[np.asarray(arm_ids, np.int64),
                                q[np.asarray(rows, np.int64), 2]]

    return FixedAnswers


KMEANS_RESTARTS = 10


def _clusters(emb: np.ndarray, k: int) -> np.ndarray:
    """The program's k-means over the history's embeddings from seeds 0 to
    ``KMEANS_RESTARTS - 1``, the assignment of least inertia kept (from
    seed 0 alone it merges three of ``hellaswag-api``'s 8 clusters; for
    ``agnews-selfhosted`` seed 0 is already the least)."""
    from repro.core.clustering import kmeans

    best, best_inertia = None, np.inf
    for seed in range(KMEANS_RESTARTS):
        assign, cents = kmeans(emb, k, seed=seed)
        inertia = float(((emb - cents[assign]) ** 2).sum())
        if inertia < best_inertia:
            best, best_inertia = assign, inertia
    return best


class Deployment:
    """The system under test for one configuration, and the benchmark's own
    calibration of the same history for the reference."""

    def __init__(self, config: dict):
        from repro.core.estimation import SuccessProbEstimator
        from repro.serving import BatchScheduler, OracleArm, ThriftRouter
        from repro.serving.feedback import FeedbackLog

        self.config = config
        pc = config["pool"]
        self.pool = Pool(**pc)
        self.budgets = [float(b) for b in config["budgets"]]
        table, emb, truth = self.pool.history(config["history"], config["history_seed"])
        self.calibration = reference.Calibration(table, emb, truth)
        self.history_counts = np.stack([(truth == c).sum() * np.ones(self.pool.num_arms)
                                        for c in self.calibration.ids])
        assign = _clusters(emb, self.pool.num_clusters)
        self.estimator = SuccessProbEstimator(table, emb, assign)
        arms = [OracleArm(f"llm-{i}", self.pool, i, metered=bool(pc["metered"]))
                for i in range(self.pool.num_arms)]
        self.engine = _answers_engine()(arms)
        self.router = ThriftRouter(self.engine, self.estimator,
                                   num_classes=self.pool.num_classes)
        fb = config.get("feedback")
        self.feedback = None if fb is None else FeedbackLog(
            self.estimator, delta=float(fb["delta"]),
            drift_delta=float(fb["drift_delta"]),
            probe_rate=float(fb["probe_rate"]), probe_seed=int(fb["probe_seed"]))
        sc = config["scheduler"]
        self.sched = BatchScheduler(
            self.router, max_batch=int(sc["max_batch"]),
            max_wait_s=float(sc["max_wait_s"]), max_inflight=int(sc["max_inflight"]),
            feedback=self.feedback)

    def traffic(self, mix: dict, seed: int, seconds: float,
                rate: Optional[float] = None):
        """The run's traffic (``traffic.generate``), with the drift arms a
        drifting mix needs, from the benchmark's own calibration."""
        arms = None
        if traffic_mod.drifts(mix):
            arms = traffic_mod.drift_arm_sets(mix, self.calibration.p,
                                              self.pool.costs, self.pool.num_classes)
        return traffic_mod.generate(mix, self.pool, self.budgets, seed, seconds,
                                    rate=rate, drift_arms=arms)

    def plan_depth(self) -> int:
        return max(len(self.router.plans.plan(c, b).order)
                   for c in self.estimator.clusters for b in self.budgets)

    def warm_programs(self) -> dict:
        """Plans for every (cluster, tier), the wave programs of the
        (batch, depth) buckets this cell's traffic can reach (none for a
        metered pool, which the reference plane serves) and, with feedback,
        the planner programs a drift replan can reach."""
        built = self.sched.prewarm(budgets=self.budgets)
        depth = self.plan_depth()
        waves = 0
        if not self.config["pool"]["metered"]:
            waves = self.router.prewarm_compile(
                int(self.config["scheduler"]["max_batch"]),
                max_waves=depth, all_batch_buckets=True)
        warm = {"plans": built, "plan_depth": depth, "wave_buckets": waves}
        if self.feedback is not None:
            warm["planner_calls"] = self.warm_planner()
        return warm

    # Monte Carlo sample counts whose compile buckets a replan reaches: the
    # program's theta is (8 + 2 eps) / (eps^2 p*) ln(2 L^2 / delta), ~8.4e3 /
    # p* at the router's eps, delta and 12 arms, so an estimate p* of the
    # best affordable arm in [0.26, 1] takes the buckets of these two
    PLANNER_THETAS = (16384, 32768)

    def warm_planner(self) -> int:
        """Run the batched planner once at every shape a drift replan can
        take: one fold drifts 1 to C clusters and rebuilds their plans at
        every tier, so G = tiers x drifted groups, at each theta bucket."""
        import jax
        from repro.core.selection import sur_greedy_many

        sel = self.router.selector
        tiers = np.asarray(self.budgets, np.float64)
        C = len(self.estimator.clusters)
        p = np.stack([st.p_hat for st in self.estimator.clusters.values()])
        calls = 0
        for d in range(1, C + 1):
            ps = np.repeat(p[:d], tiers.size, axis=0)
            budgets = np.tile(tiers, d)
            for theta in self.PLANNER_THETAS:
                sur_greedy_many(ps, sel.costs, budgets, self.pool.num_classes,
                                jax.random.key(sel.seed), theta,
                                use_kernel=sel.use_kernel)
                calls += 1
        return calls


def prepare(config: dict, mix: dict, seed: int, seconds: float,
            rate: Optional[float] = None, fault=None, rec=None):
    """Build the deployment, draw the run's traffic, warm every program it
    uses and serve the warm-up phase. Returns ``(deployment, traffic,
    warm)``. ``fault``, for the tests only, breaks the timed path (given
    the deployment) before anything is planned or served. ``rec``, a
    ``Recorder``, records the warm-up: a deployment with feedback needs it,
    since what the warm-up folds in moves the estimates the window starts
    from."""
    dep = Deployment(config)
    tr = dep.traffic(mix, seed, seconds, rate=rate)
    dep.engine.answers = tr.answers
    if fault is not None:
        fault(dep)
    warm = dep.warm_programs()
    with rec.installed() if rec is not None else contextlib.nullcontext():
        serve(dep, tr, 0, tr.n_warm, float(mix.get("warmup_s", 0.0)))
    return dep, tr, warm


# ---------------------------------------------------------------------------
# What the run records
# ---------------------------------------------------------------------------

class Recorder:
    """Wraps the program's methods for the length of a run.

    Always: every routed group as a tuple ``(perf_counter at dispatch,
    traffic row of each request (B,), planned arm per wave (T, B) with -1
    for none, plane kind)``, for the comparison. Tuples of arrays, floats
    and strings leave the garbage collector's lists, so recording adds no
    tracked object per group. In a traced run also: ``bench.*`` profiler
    spans and the host seconds of each span.

    With ``feedback``, for the reference's replay of the loop, also:

    * ``group_cids``: each routed group's cluster id per request (B,), the
      program's own ids, beside ``groups``;
    * ``id_rows``: ``(request ids, traffic rows)`` per submitted block;
    * ``labels``: ``(folds before it, request ids, labels)`` per
      ``FeedbackLog.record_many``;
    * ``folds``: ``(routed groups before it, drifted cluster ids)`` per
      ``FeedbackLog.apply``, the boundary's place among the groups;
    * ``probes``: ``(request ids, arms)`` of each group's exploration probes;
    * ``replans``: ``(perf_counter at start, plans rebuilt)`` per
      ``PlanService.replan_stale``, and ``planner``: ``(perf_counter, theta
      per group that affords an arm)`` per batched planner call;
    * in a traced run the spans ``bench.fold`` (around ``record_many`` and
      ``apply``) and ``bench.replan`` (around ``replan_stale``)."""

    def __init__(self, trace: bool, feedback: bool = False):
        self.trace = trace
        self.feedback = feedback
        self.groups: List[tuple] = []
        self.spans: Dict[str, list] = {}      # name -> [(start, end)]
        self.group_cids: List[np.ndarray] = []
        self.id_rows: List[tuple] = []
        self.labels: List[tuple] = []
        self.folds: List[tuple] = []
        self.probes: List[tuple] = []
        self.replans: List[tuple] = []
        self.planner: List[tuple] = []

    @contextlib.contextmanager
    def installed(self):
        import jax
        from repro.serving import ThriftRouter
        from repro.serving.router import PendingRoute

        rec = self
        patched = []

        def patch(cls, name, make):
            orig = cls.__dict__[name]
            setattr(cls, name, make(orig))
            patched.append((cls, name, orig))

        def span(name, orig):
            def wrapped(*a, **k):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(name):
                    out = orig(*a, **k)
                rec.spans.setdefault(name, []).append((t0, time.perf_counter()))
                return out
            return wrapped

        def begin_route(orig):
            timed = span("bench.route", orig) if rec.trace else orig

            def wrapped(router, queries, *a, **k):
                t = time.perf_counter()
                pending = timed(router, queries, *a, **k)
                if pending.kind != "empty":
                    rec.groups.append((t, np.asarray(queries)[:, 2],
                                       pending.sched_T, str(pending.kind)))
                    if rec.feedback:
                        rec.group_cids.append(np.asarray(pending.cluster_ids))
                return pending
            return wrapped

        patch(ThriftRouter, "begin_route", begin_route)
        if self.trace:
            # one span per group, never per pump or submit: the serve loop
            # calls those thousands of times a second
            patch(PendingRoute, "step", lambda o: span("bench.step", o))
            patch(PendingRoute, "result", lambda o: span("bench.finalize", o))
        if self.feedback:
            self._patch_feedback(patch, span)
        try:
            yield self
        finally:
            for cls, name, orig in reversed(patched):
                setattr(cls, name, orig)

    def _patch_feedback(self, patch, span):
        from repro.core import selection
        from repro.serving import BatchScheduler
        from repro.serving.feedback import FeedbackLog
        from repro.serving.plans import PlanService

        rec = self
        timed = (lambda name, o: span(name, o)) if self.trace else (lambda name, o: o)

        # the arrays recorded in the window are kept by reference, never
        # copied: the ids of a block and the traffic's rows and labels are
        # arrays that neither the program nor the harness writes again
        def submit_many(orig):
            def wrapped(sched, payloads, *a, **k):
                blk = orig(sched, payloads, *a, **k)
                rec.id_rows.append((blk.request_ids, payloads[:, 2]))
                return blk
            return wrapped

        def record_many(orig):
            inner = timed("bench.fold", orig)

            def wrapped(log, ids, labels):
                rec.labels.append((len(rec.folds), ids, labels))
                return inner(log, ids, labels)
            return wrapped

        def apply(orig):
            inner = timed("bench.fold", orig)

            def wrapped(log):
                report = inner(log)
                rec.folds.append((len(rec.groups), tuple(int(c) for c in report.drifted)))
                return report
            return wrapped

        def observe(orig):
            def wrapped(log, ids, clusters, schedule, responses, invoked, probes=None):
                if probes is not None and len(probes[0]):
                    rows = np.asarray(probes[0], np.int64)
                    rec.probes.append((np.asarray(ids, np.int64)[rows],
                                       np.asarray(probes[1], np.int64).copy()))
                return orig(log, ids, clusters, schedule, responses, invoked,
                            probes=probes)
            return wrapped

        def replan_stale(orig):
            inner = timed("bench.replan", orig)

            def wrapped(plans, *a, **k):
                t = time.perf_counter()
                rebuilt = inner(plans, *a, **k)
                rec.replans.append((t, int(rebuilt)))
                return rebuilt
            return wrapped

        def sur_greedy_many(orig):
            def wrapped(ps, b, budgets, *a, **k):
                t = time.perf_counter()
                thetas = a[2] if len(a) > 2 else k["thetas"]
                G = np.atleast_2d(ps).shape[0]
                afford = np.broadcast_to(np.asarray(budgets, np.float64), (G,)) \
                    >= np.min(b) - 1e-15
                rec.planner.append((t, np.broadcast_to(
                    np.asarray(thetas, np.int64), (G,))[afford].copy()))
                return orig(ps, b, budgets, *a, **k)
            return wrapped

        patch(BatchScheduler, "submit_many", submit_many)
        patch(FeedbackLog, "record_many", record_many)
        patch(FeedbackLog, "apply", apply)
        patch(FeedbackLog, "observe", observe)
        patch(PlanService, "replan_stale", replan_stale)
        patch(selection, "sur_greedy_many", sur_greedy_many)


# ---------------------------------------------------------------------------
# Serving a phase of traffic
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    t0_perf: float            # window start, time.perf_counter
    t1_perf: float            # window end, time.perf_counter
    window_s: float           # measured length
    completed_in_window: int
    backlog_at_close: int
    counters: dict            # scheduler counter deltas over the window
    lag_mean_ms: float        # how late the generator submitted, mean
    lag_max_ms: float
    submits: int
    outputs: dict             # pred, stop, cost, latency_s of rows [lo, hi)
    unfinished: int           # blocks not done after the drain
    stalls: list              # (seconds, at seconds into the window) of long loop turns
    gc: dict                  # garbage-collector passes in the window, per generation


COUNTERS = ("requests", "batches", "completed", "spec_jit", "spec_reference",
            "flushes")
# with feedback on: window counter -> the scheduler's stats key
FEEDBACK_COUNTERS = {"feedback_labels": "feedback_labels",
                     "feedback_applies": "feedback_applies",
                     "feedback_drifts": "feedback_drifts",
                     "batch_replans": "plan_batch_replans"}


def _counter_keys(sched) -> dict:
    keys = {k: k for k in COUNTERS}
    if sched.feedback is not None:
        keys.update(FEEDBACK_COUNTERS)
    return keys


def _counters(sched, keys: dict) -> dict:
    st = sched.stats
    return {k: float(st.get(v, 0)) for k, v in keys.items()}


class GcWatch:
    """Python garbage-collector passes while installed: per generation, the
    count, total and longest pause in seconds."""

    def __init__(self):
        self.passes: Dict[int, list] = {}
        self._t = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.passes.setdefault(info["generation"], []).append(
                time.perf_counter() - self._t)

    @contextlib.contextmanager
    def installed(self):
        gc.callbacks.append(self._cb)
        try:
            yield self
        finally:
            gc.callbacks.remove(self._cb)

    def summary(self) -> dict:
        return {g: {"n": len(v), "total_ms": 1e3 * sum(v), "max_ms": 1e3 * max(v)}
                for g, v in sorted(self.passes.items())}


STALL_S = 0.02            # a serve-loop iteration longer than this is a stall


def serve(dep: Deployment, tr, lo: int, hi: int, seconds: float,
          on_open=None, on_close=None) -> Served:
    """Offer rows ``[lo, hi)`` of the traffic at their due times for
    ``seconds``, then send what is left of them and drain.

    Each block's outputs are copied into arrays as soon as it is done and
    the block is let go, so the harness keeps no Python object per request
    or per group alive through the window: objects it held would push the
    program's process into full garbage-collector passes that a client
    which lets its answers go would not cause."""
    sched = dep.sched
    offs = tr.offsets[lo:hi]
    n = hi - lo
    out = {"pred": np.full(n, -1, np.int64), "stop": np.zeros(n, np.int64),
           "cost": np.zeros(n, np.float64), "latency_s": np.full(n, np.inf)}
    pending: collections.deque = collections.deque()
    lags: List[float] = []
    stalls: List[tuple] = []

    def submit(a, b, due):
        pending.append((sched.submit_many(
            tr.payloads[a:b], tr.emb[a:b], tr.budgets[a:b],
            arrival_s=due[a - lo:b - lo]), a - lo, b - lo))

    def harvest():
        while pending and pending[0][0].done():
            blk, a, b = pending.popleft()
            out["pred"][a:b] = blk.predictions
            out["stop"][a:b] = blk.stop_waves
            out["cost"][a:b] = blk.costs
            out["latency_s"][a:b] = blk.latencies_s
            if tr.labels:     # each answer read gets its true label back at once
                sched.record_outcomes(blk.request_ids, tr.payloads[lo + a:lo + b, 1])

    keys = _counter_keys(sched)
    c0 = _counters(sched, keys)
    if on_open is not None:
        on_open()
    watch = GcWatch()
    with watch.installed():
        t0_perf = time.perf_counter()
        t0 = time.monotonic()
        due = t0 + offs
        t_end = t0 + seconds
        sent = lo
        prev = t0
        while True:
            now = time.monotonic()
            if now - prev > STALL_S:
                stalls.append((now - prev, prev - t0))
            prev = now
            if now >= t_end:
                break
            k = lo + int(np.searchsorted(offs, now - t0, side="right"))
            if k > sent:
                submit(sent, k, due)
                lags.append(now - due[sent - lo])
                sent = k
            sched.pump()
            harvest()
        t1_perf = time.perf_counter()
        c1 = _counters(sched, keys)
    if on_close is not None:
        on_close()
    completed = int(c1["completed"] - c0["completed"])
    due_by_close = int(np.searchsorted(offs, now - t0, side="right"))
    if sent < hi:
        submit(sent, hi, due)
    sched.drain()
    harvest()
    lag = np.asarray(lags) * 1e3 if lags else np.zeros(1)
    return Served(
        t0_perf=t0_perf, t1_perf=t1_perf, window_s=now - t0,
        completed_in_window=completed,
        backlog_at_close=due_by_close - completed,
        counters={k: c1[k] - c0[k] for k in keys},
        lag_mean_ms=float(lag.mean()), lag_max_ms=float(lag.max()),
        submits=len(lags), outputs=out, unfinished=len(pending),
        stalls=stalls, gc=watch.summary(),
    )


# ---------------------------------------------------------------------------
# The comparison with the reference
# ---------------------------------------------------------------------------

def outcomes(dep: Deployment, served: Served, rec: Recorder, lo: int, hi: int):
    """Per-request arrays over rows ``[lo, hi)``: what the program served
    and the arm set it planned for each request."""
    n = hi - lo
    routed = np.zeros(n, bool)
    arm_set = np.zeros((n, dep.pool.num_arms), bool)
    for t, qidx, sched_T, kind in rec.groups:
        rows = qidx - lo
        keep = (rows >= 0) & (rows < n)
        if not keep.any():
            continue
        r = rows[keep]
        routed[r] = True
        sched = sched_T[:, keep]
        t_idx, b_idx = np.nonzero(sched >= 0)
        arm_set[r[b_idx], sched[t_idx, b_idx]] = True
    got = served.outputs
    return dict(done=(got["pred"] >= 0) & routed, pred=got["pred"], stop=got["stop"],
                cost=got["cost"], latency_s=got["latency_s"], arm_set=arm_set)


def compare(dep: Deployment, tr, out: dict, lo: int, hi: int,
            control: bool = False, rec: Optional[Recorder] = None,
            info: Optional[dict] = None) -> dict:
    """Readings of the program's outputs against the references; with
    ``control`` the control's readings instead: the data-plane reference
    in float32 and the planner's best affordable arm alone, put in the
    program's place. A deployment with feedback is compared through the
    reference's replay of its loop (``compare_feedback``), which needs the
    run's ``rec``, recorded from the warm-up on; it fills ``info``, where
    given, with what the reference's replay saw."""
    if dep.feedback is not None:
        return compare_feedback(dep, tr, out, lo, hi, rec, control=control,
                                info=info)
    costs = dep.pool.costs
    K = dep.pool.num_classes
    cal = dep.calibration
    row = cal.nearest(tr.emb[lo:hi])
    budgets = tr.budgets[lo:hi]
    xis = planref.xis_for(cal.p, K)
    arm_set = out["arm_set"]
    if control:
        plans = {}
        for c, b in set(zip(row.tolist(), budgets.tolist())):
            plans[c, b] = planref.best_single(xis[c], costs, b)
        arm_set = np.stack([plans[c, b] for c, b in zip(row.tolist(), budgets.tolist())])
    p = cal.p[row]
    answers = tr.answers[:, lo:hi].T
    ref = reference.route(p, arm_set, answers, costs, K)
    gap = planref.plan_gap(xis, costs, np.column_stack([row, budgets]), arm_set)
    planned = (arm_set * costs[None, :]).sum(axis=1)
    if control:
        c_pred, c_stop, c_cost = reference.route(p, arm_set, answers, costs, K,
                                                 dtype=np.float32)
        got = (c_pred, c_stop, c_cost.astype(np.float64))
        done = np.ones(hi - lo, bool)
    else:
        got = (out["pred"], out["stop"], out["cost"])
        done = out["done"]
    return check.readings(done, *got, ref, planned, budgets,
                          float(costs.min()), gap)


def replay_feedback(dep: Deployment, tr, rec: Recorder, dtype=np.float64,
                    against: Optional[list] = None):
    """The reference's run of the feedback loop over everything ``rec``
    saw (``fold.replay``), its gates held against the program's, or against
    ``against`` (calibration rows fired, per fold). Returns ``(replay,
    gate_disagreements)``."""
    cal = dep.calibration
    if not rec.groups:
        raise ValueError("nothing was routed")
    rows = np.concatenate([q for _, q, _, _ in rec.groups])
    group = np.repeat(np.arange(len(rec.groups)),
                      [q.shape[0] for _, q, _, _ in rec.groups])
    arm_set = np.zeros((rows.size, dep.pool.num_arms), bool)
    at = 0
    for _, q, sched_T, _ in rec.groups:
        t_idx, b_idx = np.nonzero(sched_T >= 0)
        arm_set[at + b_idx, sched_T[t_idx, b_idx]] = True
        at += q.shape[0]
    cluster = cal.nearest(tr.emb[rows])
    # each of the program's cluster ids stands for the calibration row most
    # of its requests fall in; an id no request was routed under, for none
    cids = np.concatenate(rec.group_cids)
    pairs, count = np.unique(np.column_stack([cids, cluster]), axis=0,
                             return_counts=True)
    name = {}
    for (c, r), k in sorted(zip(map(tuple, pairs), count), key=lambda x: x[1]):
        name[int(c)] = int(r)
    boundaries = [(g, {name.get(c, -1 - c) for c in drifted})
                  for g, drifted in rec.folds]
    if against is not None:
        boundaries = [(g, fired) for (g, _), fired in zip(boundaries, against)]
    ids = np.concatenate([i for i, _ in rec.id_rows])
    id_row = np.concatenate([r for _, r in rec.id_rows])
    order = np.argsort(ids)
    ids, id_row = ids[order], id_row[order]

    def rows_of(req_ids):
        at = np.searchsorted(ids, req_ids)
        if np.any(at >= ids.size) or np.any(ids[np.minimum(at, ids.size - 1)] != req_ids):
            raise ValueError("a request id that was never submitted")
        return id_row[at]

    labels = tr.payloads[:, 1]
    for _, req_ids, sent in rec.labels:
        if not np.array_equal(labels[rows_of(req_ids)], sent):
            raise ValueError("a label sent back is not the request's truth")
    label_events = [(f, rows_of(req_ids)) for f, req_ids, _ in rec.labels]
    probe_arm = np.full(tr.n, -1, np.int64)
    for req_ids, arms in rec.probes:
        probe_arm[rows_of(req_ids)] = arms
    rp, disagree = fold.replay(
        cal.p, dep.history_counts, float(dep.config["feedback"]["drift_delta"]),
        rows, group, cluster, arm_set, tr.answers, labels, dep.pool.costs,
        dep.pool.num_classes, boundaries, label_events, probe_arm, dtype=dtype)
    return rp, disagree


def compare_feedback(dep: Deployment, tr, out: dict, lo: int, hi: int,
                     rec: Recorder, control: bool = False,
                     info: Optional[dict] = None) -> dict:
    """``compare`` for a deployment with feedback: every request due in the
    window is routed by the reference under the snapshot its own replay of
    the loop had in force when the request's group was dispatched, and each
    planned set is scored against the least SurGreedy can return on that
    snapshot at Algorithm 3's Monte Carlo resolution (``fold.plan_gap``).
    Adds ``gate_disagreements``: the clusters, summed over the program's
    folds, whose gate the program and the reference decided differently. The control is the same replay in float32 beside
    the float64 one's gates, its data plane in float32 on the planner's
    best affordable arm alone."""
    if rec is None or not rec.feedback:
        raise ValueError("a deployment with feedback is compared from its record")
    costs = dep.pool.costs
    K = dep.pool.num_classes
    rp, disagree = replay_feedback(dep, tr, rec)
    n = hi - lo
    pos = np.full(n, -1, np.int64)
    win = (rp.rows >= lo) & (rp.rows < hi)
    pos[rp.rows[win] - lo] = np.flatnonzero(win)
    routed = pos >= 0
    snap = np.zeros(n, np.int64)
    snap[routed] = rp.snap[pos[routed]]
    snaps_p = np.stack(rp.snaps_p)
    budgets = tr.budgets[lo:hi]
    arm_set = out["arm_set"]
    answers = tr.answers[:, lo:hi].T
    p = snaps_p[snap]
    if control:
        _, disagree = replay_feedback(dep, tr, rec, dtype=np.float32,
                                      against=rp.fired_log)
        arm_set = np.zeros_like(arm_set)
        for s, b in set(zip(snap[routed].tolist(), budgets[routed].tolist())):
            est = types.SimpleNamespace(
                p=np.clip(snaps_p[s], reference.P_FLOOR, 1.0 - reference.P_FLOOR))
            arm_set[routed & (snap == s) & (budgets == b)] = planref.best_single(
                est, costs, b)
    ref = [np.zeros(n, np.int64), np.zeros(n, np.int64), np.zeros(n, np.float64)]
    if control:
        ref = list(reference.route(p, arm_set, answers, costs, K))
    else:
        ref[0][routed] = rp.pred[pos[routed]]
        ref[1][routed] = rp.stop[pos[routed]]
        ref[2][routed] = rp.cost[pos[routed]]
    gap = fold.plan_gap(rp.snaps_p, costs, K, snap[routed], budgets[routed],
                        arm_set[routed])
    planned = (arm_set * costs[None, :]).sum(axis=1)
    if control:
        c_pred, c_stop, c_cost = reference.route(p, arm_set, answers, costs, K,
                                                 dtype=np.float32)
        got = (c_pred, c_stop, c_cost.astype(np.float64))
        done = routed
    else:
        got = (out["pred"], out["stop"], out["cost"])
        done = out["done"]
    values = check.readings(done, *got, tuple(ref), planned, budgets,
                            float(costs.min()), gap)
    values["gate_disagreements"] = int(disagree)
    if info is not None:
        info.update(reference_fires=rp.fires, reference_snapshots=len(rp.snaps_p))
    return values


# ---------------------------------------------------------------------------
# One run of one cell
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    variant: Optional[str]
    counters: dict
    groups: list              # (kind, B, T) of groups dispatched in the window
    spans: dict               # name -> (n, 2) perf_counter intervals in the window
    num_classes: int
    trace: Optional[dict]
    peaks: dict
    num_arms: int = 0
    replans: list = dataclasses.field(default_factory=list)   # plans rebuilt per replan call
    planner: list = dataclasses.field(default_factory=list)   # theta per group, per planner call


def run_cell(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, t_start: float, devices, bench: dict,
             require_tpu: bool = True, fault=None) -> dict:
    """Set up, warm, serve the window, check, and return the result line.

    ``t_start`` is ``time.monotonic()`` at process start; ``fault``, for
    the tests only, is handed to ``prepare``."""
    import jax

    counter = CompileCount.get()
    rec = Recorder(trace, feedback="feedback" in config)
    dep, tr, warm = prepare(config, mix, seed, seconds, fault=fault,
                            rec=rec if rec.feedback else None)
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        programs_before = counter.n
        setup_s = time.monotonic() - t_start
        window_span = []

        def open_window():
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(tmp, profiler_options=opts)
                window_span.append(jax.profiler.TraceAnnotation("bench.window"))
                window_span[0].__enter__()

        def close_window():
            if trace:
                window_span[0].__exit__(None, None, None)
                jax.profiler.stop_trace()

        with rec.installed():
            served = serve(dep, tr, tr.n_warm, tr.n, seconds,
                           on_open=open_window, on_close=close_window)
        compiles = counter.n - programs_before
        memory = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices)
        reduced = None
        if trace:
            from . import trace as trace_mod

            reduced = trace_mod.reduce(trace_mod.load(trace_mod.find_trace(tmp)))
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    lo, hi = tr.n_warm, tr.n
    out = outcomes(dep, served, rec, lo, hi)
    t_ref = time.monotonic()
    info: dict = {}
    values = compare(dep, tr, out, lo, hi, rec=rec, info=info)
    t_ref = time.monotonic() - t_ref
    limits = config["correct_limits"]

    dev = devices[0]
    print(f"deployment: {config['name']} | warm: {warm} | requests due in window "
          f"{hi - lo} (warm-up {tr.n_warm})")
    print(f"window: {served.window_s:.4f}s | completed in window "
          f"{served.completed_in_window} | backlog at close {served.backlog_at_close} | "
          f"generator lag mean {served.lag_mean_ms:.3f}ms max {served.lag_max_ms:.3f}ms "
          f"over {served.submits} submits")
    print(f"window counters: {json.dumps(served.counters)} | programs compiled or "
          f"loaded in window {compiles}")
    if rec.feedback:
        in_window = [r for t, r in rec.replans if served.t0_perf <= t < served.t1_perf]
        print(f"feedback: drift events in window "
              f"{traffic_mod.drift_events(mix, served.window_s)} | folds {len(rec.folds)} "
              f"| replans in window {len(in_window)} rebuilding {sum(in_window)} plans "
              f"| reference gates fired {info['reference_fires']} over "
              f"{info['reference_snapshots']} snapshots")
    top = sorted(served.stalls, reverse=True)[:5]
    print(f"host stalls in window: {len(served.stalls)} serve-loop turns over "
          f"{1e3 * STALL_S:.0f} ms, longest (ms, at s) "
          f"{[(round(1e3 * d, 1), round(t, 2)) for d, t in top]} | "
          f"gc passes {json.dumps(served.gc)}")
    result = {
        "correct": check.verdict(values, limits),
        "attempted": int(hi - lo),
        "failed": int(values["missing"]),
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": memory},
    }
    lat = stats.latency_ms(out["latency_s"], out["done"])
    print(f"latency from due time: p50 {lat['p50_ms']!r} ms | p99 {lat['p99_ms']!r} ms"
          f" | reference's comparison {t_ref:.2f}s")
    if not trace:
        e2e = {"p50_ms": lat["p50_ms"],
               "served_qps": stats.served_qps(served.completed_in_window, served.window_s),
               "setup_s": setup_s}
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        in_win = lambda t: served.t0_perf <= t < served.t1_perf  # noqa: E731
        ctx_common = dict(
            counters=served.counters,
            groups=[(kind, qidx.shape[0], sched_T.shape[0])
                    for t, qidx, sched_T, kind in rec.groups if in_win(t)],
            spans={k: np.asarray([iv for iv in v if in_win(iv[0])]).reshape(-1, 2)
                   for k, v in rec.spans.items()},
            num_classes=dep.pool.num_classes, trace=reduced,
            peaks=peaks(dev.device_kind) if require_tpu else {},
            num_arms=dep.pool.num_arms,
            replans=[r for t, r in rec.replans if in_win(t)],
            planner=[th for t, th in rec.planner if in_win(t)],
        )
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            variant = m["name"].split(".", 1)[1] if "." in m["name"] else None
            value = metric_reader(m["name"])(Context(variant=variant, **ctx_common))
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        print(f"trace: chips {reduced['chips']} | busy {reduced['busy_s']!r}s of "
              f"{reduced['window_s']!r}s | programs {json.dumps(reduced['programs'])}")
    result["checks"] = check.as_json(values, limits)
    sys.stdout.flush()
    for line in check.lines(values, limits):
        print(line, file=sys.stderr)
    return result
