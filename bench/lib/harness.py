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
from typing import Dict, List, Optional

import numpy as np

from . import check, planref, reference, stats, traffic as traffic_mod
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


class Deployment:
    """The system under test for one configuration, and the benchmark's own
    calibration of the same history for the reference."""

    def __init__(self, config: dict):
        from repro.core.clustering import kmeans
        from repro.core.estimation import SuccessProbEstimator
        from repro.serving import BatchScheduler, OracleArm, ThriftRouter

        self.config = config
        pc = config["pool"]
        self.pool = Pool(**pc)
        self.budgets = [float(b) for b in config["budgets"]]
        table, emb, truth = self.pool.history(config["history"], config["history_seed"])
        self.calibration = reference.Calibration(table, emb, truth)
        assign, _ = kmeans(emb, self.pool.num_clusters, seed=0)
        self.estimator = SuccessProbEstimator(table, emb, assign)
        arms = [OracleArm(f"llm-{i}", self.pool, i, metered=bool(pc["metered"]))
                for i in range(self.pool.num_arms)]
        self.engine = _answers_engine()(arms)
        self.router = ThriftRouter(self.engine, self.estimator,
                                   num_classes=self.pool.num_classes)
        sc = config["scheduler"]
        self.sched = BatchScheduler(
            self.router, max_batch=int(sc["max_batch"]),
            max_wait_s=float(sc["max_wait_s"]), max_inflight=int(sc["max_inflight"]))

    def plan_depth(self) -> int:
        return max(len(self.router.plans.plan(c, b).order)
                   for c in self.estimator.clusters for b in self.budgets)

    def warm_programs(self) -> dict:
        """Plans for every (cluster, tier), and the wave programs of the
        (batch, depth) buckets this cell's traffic can reach (none for a
        metered pool, which the reference plane serves)."""
        built = self.sched.prewarm(budgets=self.budgets)
        depth = self.plan_depth()
        waves = 0
        if not self.config["pool"]["metered"]:
            waves = self.router.prewarm_compile(
                int(self.config["scheduler"]["max_batch"]),
                max_waves=depth, all_batch_buckets=True)
        return {"plans": built, "plan_depth": depth, "wave_buckets": waves}


def prepare(config: dict, mix: dict, seed: int, seconds: float,
            rate: Optional[float] = None, fault=None):
    """Build the deployment, draw the run's traffic, warm every program it
    uses and serve the warm-up phase. Returns ``(deployment, traffic,
    warm)``. ``fault``, for the tests only, breaks the timed path (given
    the deployment) before anything is planned or served."""
    dep = Deployment(config)
    tr = traffic_mod.generate(mix, dep.pool, dep.budgets, seed, seconds, rate=rate)
    dep.engine.answers = tr.answers
    if fault is not None:
        fault(dep)
    warm = dep.warm_programs()
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
    spans and the host seconds of each span."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.groups: List[tuple] = []
        self.spans: Dict[str, list] = {}      # name -> [(start, end)]

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
                return pending
            return wrapped

        patch(ThriftRouter, "begin_route", begin_route)
        if self.trace:
            # one span per group, never per pump or submit: the serve loop
            # calls those thousands of times a second
            patch(PendingRoute, "step", lambda o: span("bench.step", o))
            patch(PendingRoute, "result", lambda o: span("bench.finalize", o))
        try:
            yield self
        finally:
            for cls, name, orig in reversed(patched):
                setattr(cls, name, orig)


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


def _counters(sched) -> dict:
    st = sched.stats
    return {k: float(st.get(k, 0)) for k in COUNTERS}


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

    c0 = _counters(sched)
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
        c1 = _counters(sched)
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
        counters={k: c1[k] - c0[k] for k in COUNTERS},
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
            control: bool = False) -> dict:
    """Readings of the program's outputs against the references; with
    ``control`` the control's readings instead: the data-plane reference
    in float32 and the planner's best affordable arm alone, put in the
    program's place."""
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


def run_cell(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, t_start: float, devices, bench: dict,
             require_tpu: bool = True, fault=None) -> dict:
    """Set up, warm, serve the window, check, and return the result line.

    ``t_start`` is ``time.monotonic()`` at process start; ``fault``, for
    the tests only, is handed to ``prepare``."""
    import jax

    counter = CompileCount.get()
    dep, tr, warm = prepare(config, mix, seed, seconds, fault=fault)
    rec = Recorder(trace)
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
    values = compare(dep, tr, out, lo, hi)
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
    print(f"latency from due time: p50 {lat['p50_ms']!r} ms | p99 {lat['p99_ms']!r} ms")
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
