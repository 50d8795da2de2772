"""Readings that set the limits of ``correct``: the program's and the
control's, over many seeds, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 4

For each seed the cell is run as ``run.py`` runs it (its own deployment,
rate, warm-up and a window of ``--seconds``, drained), and two comparisons
are read with the same code (``lib/check.py``):

* ``program``: what the program served and planned against the float64
  references;
* ``control``: in the program's place, the data-plane reference computed in
  float32 (the precision below the configuration's float64) on the arm set
  of the planner's control, the best affordable arm alone; with feedback,
  also the reference's fold and gate in float32, held against its float64
  gates.

A limit lies above every program reading and below every control reading.
Prints one JSON line per seed. Exits non-zero without a TPU.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(config, mix, seed, seconds):
    """``(program, control, info)`` readings of one seed's run."""
    from bench.lib import harness

    rec = harness.Recorder(False, feedback="feedback" in config)
    dep, tr, _ = harness.prepare(config, mix, seed, seconds,
                                 rec=rec if rec.feedback else None)
    with rec.installed():
        served = harness.serve(dep, tr, tr.n_warm, tr.n, seconds)
    lo, hi = tr.n_warm, tr.n
    out = harness.outcomes(dep, served, rec, lo, hi)
    info = {"due": hi - lo}
    program = harness.compare(dep, tr, out, lo, hi, rec=rec, info=info)
    control = harness.compare(dep, tr, out, lo, hi, control=True, rec=rec)
    return program, control, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    from bench.lib import harness

    cell, config, mix = harness.load_cell(args.workload)
    try:
        harness.start_jax(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        program, control, info = readings(config, mix, seed, args.seconds)
        print(json.dumps({"seed": seed, "program": program, "control": control,
                          **info}))
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
