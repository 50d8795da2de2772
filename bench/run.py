"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: load the cell's data, build and calibrate the deployment,
warm every program the cell's traffic uses, serve the measured window,
compare every request due in it with the plain reference, and print, as the
last line of standard output, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in a
traced run), then ``checks``: each number compared, with its limit. The
same numbers end standard error. Exits non-zero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import harness

    bench = harness.load_benchmark()
    cell, config, mix = harness.load_cell(args.workload, bench)
    try:
        devices = harness.start_jax(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, config, mix, args.seed, args.seconds,
                              bool(args.trace), T_START, devices, bench)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
