"""Split one cell's host time by the program's own spans.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> [--out FILE]

Serves the cell as a traced run of ``bench/run.py`` does (the same set-up,
warm-up, window, profiler options and comparison with the reference), with
the program's telemetry (``repro.serving.telemetry``) on for the window
only. Prints one JSON object as its last line (and writes it to ``--out``
if given):

* ``families``: host ms per group of each program step (admission,
  planning, gather, launch, the wait on the device, retirement), the queue
  wait per request and program spans over 20 ms per 1000 groups
  (``lib/spans.py``);
* ``route_host_ms``: the accepted metric's outside timing of the same
  window, and ``route_coverage``: plan + gather + launch over it;
* ``idle_s`` and ``idle_shares``: the device's idle time by the innermost
  host span over it, ``thrift.*`` and ``bench.*``;
* ``slow_spans``, ``prefetch_ms_by_quarter``, the serve loop's stalls, the
  end-to-end readings of the window and ``correct``.

Exits 2 where JAX finds no TPU, or where the program has no telemetry.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

COUNTERS = ("requests", "batches", "spec_jit", "queue_wait_s")


def measure(config: dict, mix: dict, seed: int, seconds: float,
            require_tpu: bool = True) -> dict:
    import jax
    import numpy as np
    from repro.serving import telemetry

    from bench.lib import check, harness, spans, stats, trace
    from bench.lib.peaks import peaks

    rec = harness.Recorder(True, feedback="feedback" in config)
    dep, tr, warm = harness.prepare(config, mix, seed, seconds,
                                    rec=rec if rec.feedback else None)
    tmp = tempfile.mkdtemp(prefix="bench_spans_")
    marks = {}

    def counters():
        st = dep.sched.stats
        return {k: float(st.get(k, 0)) for k in COUNTERS}

    def open_window():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        marks["window"] = jax.profiler.TraceAnnotation(trace.WINDOW)
        marks["window"].__enter__()
        marks["c0"], marks["s0"] = counters(), telemetry.snapshot()
        telemetry.enable(True)

    def close_window():
        telemetry.enable(False)
        marks["c1"], marks["s1"] = counters(), telemetry.snapshot()
        marks["window"].__exit__(None, None, None)
        jax.profiler.stop_trace()

    try:
        with rec.installed():
            served = harness.serve(dep, tr, tr.n_warm, tr.n, seconds,
                                   on_open=open_window, on_close=close_window)
        path = trace.find_trace(tmp)
        raw = trace.load(path)
        raw["host"] = spans.host_events(path)
    finally:
        telemetry.enable(False)
        shutil.rmtree(tmp, ignore_errors=True)

    lo, hi = tr.n_warm, tr.n
    out = harness.outcomes(dep, served, rec, lo, hi)
    values = harness.compare(dep, tr, out, lo, hi, rec=rec)
    span_delta = spans.delta(marks["s0"], marks["s1"])
    cnt = {k: marks["c1"][k] - marks["c0"][k] for k in COUNTERS}
    fam = spans.families(span_delta, cnt)
    reduced = trace.reduce(raw)
    in_win = lambda t: served.t0_perf <= t < served.t1_perf  # noqa: E731
    ctx = harness.Context(
        variant=None, counters=served.counters,
        groups=[(kind, q.shape[0], s.shape[0])
                for t, q, s, kind in rec.groups if in_win(t)],
        spans={k: np.asarray([iv for iv in v if in_win(iv[0])]).reshape(-1, 2)
               for k, v in rec.spans.items()},
        num_classes=dep.pool.num_classes, trace=reduced,
        peaks=peaks(jax.devices()[0].device_kind) if require_tpu else {},
    )
    route_ms = harness.metric_reader("route_host_ms")(ctx)
    routed = [fam[k] for k in ("plan_host_ms", "gather_host_ms", "launch_host_ms")]
    idle = spans.idle_by_span(raw)
    lat = stats.latency_ms(out["latency_s"], out["done"])
    dev = jax.devices()[0]
    return {
        "correct": check.verdict(values, config["correct_limits"]),
        "warm": warm,
        "window_s": served.window_s,
        "p50_ms": lat["p50_ms"], "p99_ms": lat["p99_ms"],
        "served_qps": stats.served_qps(served.completed_in_window, served.window_s),
        "counters": cnt,
        "families": fam,
        "route_host_ms": route_ms,
        "route_coverage": (sum(routed) / route_ms
                           if route_ms and None not in routed else None),
        "spans": span_delta,
        "busy_s": reduced["busy_s"], "trace_window_s": reduced["window_s"],
        "idle_pct": reduced["idle_pct"],
        "idle_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_shares": spans.idle_shares(idle),
        "slow_spans": spans.slow_spans(raw["host"], telemetry.SLOW_S)[:20],
        "prefetch_ms_by_quarter": spans.by_quarter(raw["host"], "thrift.prefetch"),
        "loop_stalls": sorted([[1e3 * d, t] for d, t in served.stalls],
                              reverse=True)[:10],
        "n_loop_stalls": len(served.stalls),
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "checks": check.as_json(values, config["correct_limits"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench.lib import harness

    try:
        import repro.serving.telemetry  # noqa: F401
    except ImportError as e:
        print(f"spans: the program has no telemetry: {e}", file=sys.stderr)
        return 2
    cell, config, mix = harness.load_cell(args.workload)
    try:
        harness.start_jax(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"spans: {e}", file=sys.stderr)
        return 2
    result = measure(config, mix, args.seed, args.seconds)
    result["setup_and_run_s"] = time.monotonic() - T_START
    line = json.dumps(result)
    if args.out:
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
