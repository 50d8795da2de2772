"""Find a cell's knee: serve its traffic at a list of offered rates, in one
process, and print what each rate gave.

    python3 bench/sweep.py --workload <cell> --rates 4000,8000,... \
        --seconds 5 --seed 1

The deployment is built and warmed once; each rate then gets a fresh
window of the cell's traffic at that rate, drained after the window. Per
rate: requests due, completions inside the window as a rate, the backlog
at the close, p50/p99 from due time over every request due (stragglers
included), how late the generator ran, and whether every request matched
the reference. The knee is the highest rate whose completions keep up
(backlog at the close under 1% of the requests due) and whose p99 stays
under the limit ``PERF.md`` records for the cell. Exits non-zero without a
TPU.

A deployment with feedback carries state from one window to the next (the
estimates its labels move), and its check replays the loop from the
calibration on: each rate then gets a deployment of its own, built, warmed
and warmed up as ``run.py`` does, the programs compiled once per process.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _window(dep, tr, seconds, rec):
    from bench.lib import harness

    with rec.installed():
        served = harness.serve(dep, tr, tr.n_warm, tr.n, seconds)
    out = harness.outcomes(dep, served, rec, tr.n_warm, tr.n)
    return served, out, harness.compare(dep, tr, out, tr.n_warm, tr.n, rec=rec)


def sweep(cell, config, mix, rates, seconds, seed):
    from bench.lib import check, harness, stats

    feedback = "feedback" in config
    if not feedback:
        dep, _, warm = harness.prepare(config, mix, seed, 0.0, rate=rates[0])
        print(f"sweep {cell['name']}: set-up {time.monotonic() - T_START:.1f}s, "
              f"warm {warm}")
    rows = []
    for i, rate in enumerate(rates):
        rec = harness.Recorder(False, feedback=feedback)
        if feedback:
            t0 = time.monotonic()
            dep, tr, warm = harness.prepare(config, mix, seed + 1 + i, seconds,
                                            rate=rate, rec=rec)
            print(f"sweep {cell['name']} at {rate}: set-up "
                  f"{time.monotonic() - t0:.1f}s, warm {warm}")
        else:
            tr = dep.traffic(dict(mix, warmup_s=0.0), seed + 1 + i, seconds, rate=rate)
            dep.engine.answers = tr.answers
        served, out, values = _window(dep, tr, seconds, rec)
        lat = stats.latency_ms(out["latency_s"], out["done"])
        row = {
            "rate_qps": rate, "due": tr.n - tr.n_warm,
            "completed_qps": served.completed_in_window / served.window_s,
            "backlog_at_close": served.backlog_at_close,
            "p50_ms": lat["p50_ms"], "p99_ms": lat["p99_ms"],
            "lag_mean_ms": served.lag_mean_ms, "lag_max_ms": served.lag_max_ms,
            "rows_per_group": served.counters["requests"] / max(served.counters["batches"], 1),
            "stalls": len(served.stalls),
            "longest_stall_ms": 1e3 * max([d for d, _ in served.stalls], default=0.0),
            "correct": check.verdict(values, config["correct_limits"]),
            "checks": {k: values[k] for k in check.names(config["correct_limits"])},
            "counters": served.counters,
        }
        rows.append(row)
        print(json.dumps(row))
        sys.stdout.flush()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated qps")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench.lib import harness

    cell, config, mix = harness.load_cell(args.workload)
    try:
        harness.start_jax(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    rates = [float(r) for r in args.rates.split(",")]
    sweep(cell, config, mix, rates, args.seconds, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
