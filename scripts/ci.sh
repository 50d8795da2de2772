#!/usr/bin/env bash
# CI: hygiene guards, the thriftlint static-analysis gate (zero findings
# across every rule including the PR 10 donation-contract pass, every
# suppression reasoned), router/serving/replica correctness, the
# multi-device replica suite under 4 forced host devices (overlapped
# placement bit-identity, fault-grid equivalence, zero timed recompiles —
# must RUN, not skip), a
# no-skip gate on the property suites (hypothesis or the in-repo fallback
# engine — they must RUN; the cost-ledger and replica shard-merge suites
# gate here too), a serving-throughput
# smoke — also under 4 forced host devices so the cross-device curve is
# exercised — (one-shot engines + the steady-state continuous-batching
# path + the online feedback-vs-drift section + the fault-tolerance
# section + the replica-scaling sweep + the cross_device subsection + the
# raw-speed section + the compile-sentinel budget) with JSON well-formedness and
# history-preservation assertions, a docs link check plus a docs symbol
# check (every doc-mentioned repro.* identifier must resolve against the
# tree), then the FULL tier-1
# suite — tracer-leak-guarded via tests/conftest.py — with zero tolerated
# failures; there is no allowlist of known-bad tests.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

# hygiene: compiled artifacts must never be tracked again (they were, once)
if git ls-files | grep -E '(^|/)__pycache__/|\.py[co]$' >/dev/null; then
    echo "FAIL: tracked __pycache__/*.pyc artifacts:" >&2
    git ls-files | grep -E '(^|/)__pycache__/|\.py[co]$' >&2
    exit 1
fi
echo "pycache hygiene OK"

# thriftlint: the jit/determinism contracts gate statically. Exit is
# non-zero on any finding — including reason-less suppression comments,
# which surface as bad-suppression findings and cannot be silenced.
python scripts/lint.py
echo "thriftlint OK (zero findings)"

python -m pytest -x -q tests/test_router_batched.py tests/test_serving.py \
    tests/test_scheduler_continuous.py tests/test_plans.py \
    tests/test_core_selection.py tests/test_feedback.py \
    tests/test_selection_batched.py tests/test_failover.py \
    tests/test_replica.py

# multi-device replica placement: force 4 host CPU devices (XLA_FLAGS, set
# before JAX starts) so the overlapped placement path is
# real, not the single-device fallback. These tests skip themselves below
# 2 devices — a skip here means the forcing flag stopped working; fail
# loudly instead of silently testing nothing.
DEV_OUT=$(XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
    python -m pytest -q -rs tests/test_replica_devices.py 2>&1) || {
    echo "$DEV_OUT"; exit 1; }
echo "$DEV_OUT" | tail -1
if echo "$DEV_OUT" | grep -qiE "skipped"; then
    echo "FAIL: device-placement tests were skipped — forced host devices" \
         "did not take effect" >&2
    echo "$DEV_OUT" >&2
    exit 1
fi
echo "multi-device replica suite ran on 4 forced devices (no skips)"

# property suites must RUN — on the real hypothesis engine when installed,
# on the in-repo tests/_hypolite.py fallback otherwise. A skip here means
# the importorskip hole is back; fail loudly instead of masking it. (This
# is their one gated run; the fast batch above deliberately omits them.)
PROP_OUT=$(python -m pytest -q -rs tests/test_properties.py \
    tests/test_estimation_properties.py tests/test_cost_ledger.py \
    tests/test_replica_merge.py 2>&1) || {
    echo "$PROP_OUT"; exit 1; }
echo "$PROP_OUT" | tail -1
if echo "$PROP_OUT" | grep -qiE "skipped"; then
    echo "FAIL: property tests were skipped — they must always run" >&2
    echo "$PROP_OUT" >&2
    exit 1
fi
echo "property suites ran (no skips)"

# serving-throughput smoke: the benchmark must run end to end — including
# the steady-state continuous-batching scheduler path and the online
# feedback-vs-drift section — and write a well-formed report (without
# clobbering the committed trajectory). The pre-seeded stub verifies the
# history-preservation contract: an existing report must fold into the new
# file's `history`, never be clobbered.
SMOKE_OUT="${TMPDIR:-/tmp}/BENCH_serving_smoke.json"
rm -f "$SMOKE_OUT"
printf '%s' '{"engine": "ci-history-stub", "rows": [{"batch": 1, "qps": 1.0}]}' \
    > "$SMOKE_OUT"
# forced host devices so the cross-device curve measures real overlapped
# placement rather than reporting {"skipped": true} on a 1-device process
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
    python -m benchmarks.serving_throughput --smoke --out "$SMOKE_OUT"
SMOKE_OUT="$SMOKE_OUT" python - <<'PY'
import json, os
report = json.load(open(os.environ["SMOKE_OUT"]))
assert report["bench"] == "serving_throughput", "unexpected bench name"
assert report["rows"], "bench report has no rows"
for row in report["rows"]:
    for key in ("batch", "qps", "wavefront_qps", "seed_qps", "accuracy"):
        assert key in row, f"bench row missing {key}"
        assert row[key] > 0 or key == "accuracy", f"bench row has bad {key}"
steady = report["steady_state"]
for key in ("saturated_qps", "oneshot_qps", "vs_jit_engine", "steady_qps",
            "p50_ms", "p99_ms", "accuracy"):
    assert key in steady, f"steady_state missing {key}"
    assert steady[key] > 0, f"steady_state has bad {key}"
assert steady["spec_jit"] + steady["spec_reference"] > 0, "no groups routed"

# the online-feedback drift section: present, well-formed, and directionally
# right even at smoke scale (the committed full-size report carries the
# >= 0.9 oracle-recovery acceptance bar)
fb = report["feedback"]
for key in ("online_acc", "oracle_acc", "frozen_acc", "recovery",
            "frozen_vs_oracle", "steady_overhead_vs_frozen", "replan_time_s",
            "feedback_labels", "feedback_drifts", "plan_stale_dropped",
            "estimator_version", "acc_trajectory"):
    assert key in fb, f"feedback missing {key}"
for key in ("online_acc", "oracle_acc", "frozen_acc"):
    assert 0.0 < fb[key] <= 1.0, f"feedback has bad {key}: {fb[key]}"
assert fb["feedback_labels"] > 0, "no labels flowed through the loop"
assert fb["feedback_drifts"] > 0, "drift never detected on drifted traffic"
assert fb["plan_stale_dropped"] > 0, "drift never re-selected a plan"
assert fb["plan_batch_replans"] > 0, "drift replans did not go batched"
assert fb["plan_batch_replanned"] >= fb["plan_batch_replans"], \
    "batched replans rebuilt nothing"
assert fb["estimator_version"] > 0, "estimator never versioned"
assert fb["online_acc"] > fb["frozen_acc"], "feedback did not beat frozen plans"
assert fb["recovery"] > fb["frozen_vs_oracle"], "no recovery over frozen"

# the batched-planner replan section: serial vs batched drift-replan
# latency, bit-identical plans, and a real speedup at the largest G (the
# committed full-size report carries the >= 3x acceptance bar at G = 64)
sel = report["selection"]
for key in ("rows", "pool", "groups_max", "speedup_at_max", "plans_match"):
    assert key in sel, f"selection missing {key}"
assert sel["rows"], "selection section has no rows"
for row in sel["rows"]:
    for key in ("groups", "serial_s", "batched_s", "speedup"):
        assert key in row, f"selection row missing {key}"
    assert row["serial_s"] > 0 and row["batched_s"] > 0, "bad replan timing"
    assert row["replanned_batched"] == row["groups"], "replan did not cover G"
assert sel["plans_match"], "batched planner diverged from serial plans"
assert sel["groups_max"] >= 8, "no multi-group replan measured"
# the >= 3x speedup acceptance bar lives in the committed full-size report;
# a wall-clock assert at smoke scale would make CI flaky on loaded hosts
assert sel["speedup_at_max"] > 0, "replan timing is malformed"

# the raw-speed section (PR 10): fused on-device planner vs the PR 9
# host-gamma plane with bit-identical plans, donated vs non-donated wave
# dispatch bit-checked, and zero recompiles inside the section's timed
# loops. The
# >= 1.3x planner bar at G = 64 lives in the committed full-size report;
# wall-clock bars at smoke scale would make CI flaky on loaded hosts.
raw = report["raw_speed"]
for key in ("planner", "donation", "timed_recompiles"):
    assert key in raw, f"raw_speed missing {key}"
pl = raw["planner"]
assert pl["rows"], "raw_speed planner has no rows"
for row in pl["rows"]:
    for key in ("groups", "hostgamma_s", "fused_s", "speedup"):
        assert key in row, f"raw_speed planner row missing {key}"
    assert row["hostgamma_s"] > 0 and row["fused_s"] > 0, "bad planner timing"
assert pl["plans_match"], "fused planner diverged from the host-gamma plane"
assert pl["groups_max"] >= 8, "raw_speed planner never measured multi-group"
dn = raw["donation"]
assert dn["bit_identical"], "donated wave dispatch diverged from nodonate"
assert dn["donate_s"] > 0 and dn["nodonate_s"] > 0, "bad donation timing"
assert raw["timed_recompiles"] == 0, \
    f"recompiles inside raw_speed timed loops: {raw['timed_recompiles']}"

# the fault-tolerance section: present, well-formed, failures really
# injected and folded; directionally right even at smoke scale (the
# committed full-size report carries the >= 0.8 replan-recovery acceptance
# bar under the 2-arm outage)
ft = report["fault_tolerance"]
for key in ("dead_arms", "baseline_acc", "frozen_acc", "failover_acc",
            "replan_acc", "frozen_recovery", "failover_recovery",
            "replan_recovery", "acc_trajectory", "p99_ms",
            "degradation_failures", "feedback_drifts"):
    assert key in ft, f"fault_tolerance missing {key}"
assert len(ft["dead_arms"]) == 2, "outage must kill exactly two arms"
for key in ("baseline_acc", "frozen_acc", "failover_acc", "replan_acc"):
    assert 0.0 < ft[key] <= 1.0, f"fault_tolerance has bad {key}: {ft[key]}"
assert ft["degradation_failures"] > 0, "outage produced no fault evidence"
assert ft["feedback_drifts"] > 0, "fault evidence never drifted the estimator"
assert ft["baseline_acc"] > ft["frozen_acc"], "outage did not hurt frozen plans"
assert ft["replan_acc"] >= ft["frozen_acc"], "replanning lost to frozen plans"
for name, p99 in ft["p99_ms"].items():
    assert p99 > 0, f"fault_tolerance p99 malformed for {name}"

# the R-replica scaling section: present, well-formed, the R=1 row
# bit-matched against the plain BatchScheduler steady path, fusion really
# fired at R > 1, and zero recompiles inside the timed sweep (the >= 2x
# aggregate-qps acceptance bar at R=4 lives in the committed full-size
# report; a wall-clock assert at smoke scale would make CI flaky)
rs = report["replica_scaling"]
for key in ("per_replica_batch", "queries", "rows", "r1_bitmatch_steady",
            "speedup_at_max", "replicas_max", "timed_recompiles"):
    assert key in rs, f"replica_scaling missing {key}"
assert rs["rows"], "replica_scaling has no rows"
for row in rs["rows"]:
    for key in ("replicas", "per_replica_batch", "qps", "p50_ms", "p99_ms",
                "speedup_vs_r1", "fused_dispatches", "fused_rows", "spills",
                "accuracy"):
        assert key in row, f"replica_scaling row missing {key}"
    assert row["qps"] > 0 and row["p99_ms"] > 0, "bad replica_scaling row"
assert rs["rows"][0]["replicas"] == 1, "replica_scaling must anchor at R=1"
assert rs["rows"][0]["fused_dispatches"] == 0, \
    "R=1 must never fuse (bit-identity contract with the steady path)"
assert any(r["replicas"] > 1 and r["fused_dispatches"] > 0
           for r in rs["rows"]), "fusion never fired at R > 1"
assert rs["replicas_max"] >= 4, "sweep did not reach R=4"
assert rs["r1_bitmatch_steady"], "ReplicaSet R=1 diverged from BatchScheduler"
assert rs["timed_recompiles"] == 0, \
    f"recompiles inside the replica sweep: {rs['timed_recompiles']}"
assert rs["speedup_at_max"] > 0, "replica scaling timing is malformed"

# the cross-device subsection: overlapped per-device placement vs the fused
# single-device anchor at R in {1, 2, 4} on the 4 forced host devices. The
# correctness bars gate unconditionally (R=1 bit-match vs the plain
# BatchScheduler, zero timed recompiles, rows well-formed); the >= 1.5x
# aggregate-qps bar gates only when the host can actually run the device
# programs in parallel (host_cores >= devices) — forced host devices
# multiplex one physical core on a 1-core CI box, where overlapped
# dispatch cannot beat fused no matter how the code is shaped, and a bar
# that can never pass is a bar nobody reads.
cd = rs["cross_device"]
for key in ("devices", "host_cores", "parallel_capable", "rows",
            "wave_plane", "overlapped_vs_fused_at_max",
            "wave_overlapped_vs_fused_at_max", "replicas_max",
            "r1_bitmatch", "timed_recompiles"):
    assert key in cd, f"cross_device missing {key}"
assert not cd.get("skipped"), "cross_device skipped despite forced devices"
assert cd["devices"] >= 4, f"forced 4 devices, saw {cd['devices']}"
assert sorted(r["replicas"] for r in cd["rows"]) == [1, 2, 4], \
    "cross_device must sweep R in {1, 2, 4}"
for row in cd["rows"]:
    for key in ("replicas", "devices_used", "qps_overlapped", "qps_fused",
                "overlapped_vs_fused", "overlapped_dispatches"):
        assert key in row, f"cross_device row missing {key}"
    assert row["qps_overlapped"] > 0 and row["qps_fused"] > 0, \
        "bad cross_device row"
    assert row["devices_used"] == min(row["replicas"], cd["devices"]), \
        "overlapped placement did not spread across the forced devices"
assert cd["rows"][-1]["overlapped_dispatches"] > 0, \
    "overlapped placement never dispatched at R=4"
wp = cd["wave_plane"]
assert wp["rows"], "cross_device wave-plane curve is empty"
for row in wp["rows"]:
    assert row["qps_overlapped_rows"] > 0 and row["qps_fused_rows"] > 0, \
        "bad cross_device wave-plane row"
assert cd["replicas_max"] >= 4, "cross_device sweep did not reach R=4"
assert cd["r1_bitmatch"], \
    "overlapped R=1 diverged from the plain BatchScheduler"
assert cd["timed_recompiles"] == 0, \
    f"recompiles inside the cross-device sweep: {cd['timed_recompiles']}"
if cd["parallel_capable"]:
    assert cd["overlapped_vs_fused_at_max"] >= 1.5, (
        f"overlapped R={cd['replicas_max']} only "
        f"{cd['overlapped_vs_fused_at_max']:.2f}x over fused on "
        f"{cd['host_cores']} cores")

# the compile-sentinel budget: every XLA compile of the wave/planner
# programs must land in a per-bucket warm-up (zero in timed sections) and
# total program counts must stay within the declared bucket budgets
cs = report["compile_sentinel"]
for key in ("timed_recompiles", "wave_compiles", "wave_bucket_budget",
            "plan_compiles", "plan_bucket_budget", "within_budget"):
    assert key in cs, f"compile_sentinel missing {key}"
assert cs["timed_recompiles"] == 0, \
    f"recompilation inside a timed section: {cs['timed_recompiles']}"
assert cs["wave_compiles"] > 0, "sentinel saw no wave compiles at all"
assert cs["within_budget"], (
    f"compile budget exceeded: wave {cs['wave_compiles']}/"
    f"{cs['wave_bucket_budget']}, plan {cs['plan_compiles']}/"
    f"{cs['plan_bucket_budget']}")

# history preservation: the pre-existing report (the stub seeded above)
# must survive as a history entry
hist = report["history"]
assert isinstance(hist, list) and hist, "prior report was clobbered, not kept"
assert hist[-1].get("engine") == "ci-history-stub", f"history lost: {hist[-1]}"

print("serving smoke OK:", [(r["batch"], round(r["qps"])) for r in report["rows"]],
      "| steady", round(steady["saturated_qps"]),
      f"({steady['vs_jit_engine']:.2f}x jit), p99 {steady['p99_ms']:.2f}ms",
      f"| feedback recovery {fb['recovery']:.2f} (frozen {fb['frozen_vs_oracle']:.2f})",
      f"| fault recovery {ft['replan_recovery']:.2f} (frozen {ft['frozen_recovery']:.2f})",
      f"| batched replan {sel['speedup_at_max']:.2f}x at G={sel['groups_max']}",
      f"| raw planner {pl['speedup_at_max']:.2f}x at G={pl['groups_max']}"
      f" (plans match {pl['plans_match']}, donation bit-id {dn['bit_identical']})",
      f"| replicas {rs['speedup_at_max']:.2f}x at R={rs['replicas_max']}"
      f" (R=1 bitmatch {rs['r1_bitmatch_steady']})",
      f"| compiles wave {cs['wave_compiles']}/{cs['wave_bucket_budget']}"
      f" plan {cs['plan_compiles']}/{cs['plan_bucket_budget']}, timed 0")
PY

# docs link check: README.md / docs/serving.md / docs/analysis.md must not
# reference files that do not exist in the repo
python - <<'PY'
import pathlib, re, sys
bad = []
for doc in ("README.md", "docs/serving.md", "docs/analysis.md"):
    text = pathlib.Path(doc).read_text()
    refs = set(re.findall(r"`([A-Za-z0-9_./-]+\.(?:py|md|sh|json))`", text))
    refs |= set(re.findall(r"\]\(([A-Za-z0-9_./-]+\.md)\)", text))
    for ref in refs:
        if not pathlib.Path(ref).exists():
            bad.append((doc, ref))
if bad:
    sys.exit(f"dangling doc references: {bad}")
print("docs link check OK")
PY

# docs symbol check: every `repro.*` identifier the docs mention must
# resolve against the tree — as an importable module, or as an attribute
# (class, function, constant) of one. Catches docs drifting ahead of (or
# behind) the code: a doc naming repro.distributed.sharding.replica_mesh
# fails here until that symbol actually exists.
python - <<'PY'
import importlib, pathlib, re, sys
names = set()
for doc in ("README.md", "docs/serving.md", "docs/analysis.md"):
    text = pathlib.Path(doc).read_text()
    names |= set(re.findall(r"`(repro(?:\.[A-Za-z_][A-Za-z0-9_]*)+)`", text))
bad = []
for name in sorted(names):
    parts = name.split(".")
    obj = None
    depth = 0
    for i in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:i]))
            depth = i
            break
        except ImportError:
            continue
    if obj is None:
        bad.append(name)
        continue
    for attr in parts[depth:]:
        try:
            obj = getattr(obj, attr)
        except AttributeError:
            bad.append(name)
            break
if bad:
    sys.exit(f"docs name repro.* symbols that do not resolve: {bad}")
print(f"docs symbol check OK ({len(names)} repro.* identifiers resolve)")
PY

# tier-1: the whole suite gates — zero failures, no exceptions
python -m pytest -q
echo "tier-1 green"
