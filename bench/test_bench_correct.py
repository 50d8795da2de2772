"""The comparison that decides ``correct``: the plain reference agrees
with the paper's loop, a sound run comes out correct, and the control and
each fault of the timed path (the planner's and the feedback loop's
included) come out not correct. Runs the harness on the CPU at a small rate
(the look for a chip is skipped, and so is the warm-up of the planner's
compile buckets, which only keeps compiles out of a chip's window)."""
import json
import time

import numpy as np
import pytest

from bench.lib import check, harness, reference

BENCH = harness.load_benchmark()


def test_reference_is_the_papers_adaptive_invocation():
    from repro.core.selection import adaptive_invoke

    rng = np.random.default_rng(0)
    N, L, K = 400, 12, 4
    p = rng.uniform(0.3, 0.97, (N, L))
    arm_set = rng.random((N, L)) < 0.5
    arm_set[:, 0] = True
    answers = rng.integers(0, K, (N, L))
    costs = rng.uniform(1e-6, 1e-4, L)
    pred, stop, cost = reference.route(p, arm_set, answers, costs, K)
    for i in range(N):
        inv = adaptive_invoke(list(np.flatnonzero(arm_set[i])), p[i], K,
                              lambda a: int(answers[i, a]), costs=costs)
        assert inv.prediction == pred[i] and len(inv.used) == stop[i]
        assert inv.cost == pytest.approx(cost[i], rel=1e-15, abs=0)


def _run(cell, fault=None, monkeypatch=None, seconds=0.8, seed=2**31 + 11, **mix_keys):
    w, config, mix = harness.load_cell(cell, BENCH)
    mix = dict(mix, **{"rate_qps": 300.0, "warmup_s": 0.3, **mix_keys})
    if monkeypatch is not None:
        monkeypatch.setattr(harness.Deployment, "warm_planner", lambda self: 0)
    devices = harness.start_jax(w["chips"], require_tpu=False)
    return harness.run_cell(w, config, mix, seed, seconds, False,
                            time.monotonic(), devices, BENCH,
                            require_tpu=False, fault=fault)


CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    res = _run(cell, monkeypatch=monkeypatch)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 100 and res["failed"] == 0


def test_control_in_float32_fails_the_cost_gap():
    w, config, mix = harness.load_cell("agnews-poisson", BENCH)
    mix = dict(mix, rate_qps=300.0, warmup_s=0.2)
    harness.start_jax(1, require_tpu=False)
    dep, tr, _ = harness.prepare(config, mix, 2**31 + 5, 0.6)
    rec = harness.Recorder(False)
    with rec.installed():
        served = harness.serve(dep, tr, tr.n_warm, tr.n, 0.6)
    out = harness.outcomes(dep, served, rec, tr.n_warm, tr.n)
    sound = harness.compare(dep, tr, out, tr.n_warm, tr.n)
    control = harness.compare(dep, tr, out, tr.n_warm, tr.n, control=True)
    limits = config["correct_limits"]
    assert check.verdict(sound, limits), sound
    assert control["cost_gap"] > 1e3 * limits["cost_gap"]
    assert control["plan_xi_gap"] > limits["plan_xi_gap"]


def _best_single_planned(monkeypatch):
    """The planner keeps only the best affordable arm of each selection."""
    import dataclasses

    from repro.core.selection import ThriftLLM

    orig_one, orig_many = ThriftLLM.select, ThriftLLM.select_many

    def cut(res):
        return dataclasses.replace(res, chosen=np.asarray([res.l_star], np.int64))

    def apply(dep):
        monkeypatch.setattr(ThriftLLM, "select",
                            lambda self, *a, **k: cut(orig_one(self, *a, **k)))
        monkeypatch.setattr(ThriftLLM, "select_many",
                            lambda self, *a, **k: [cut(r) for r in orig_many(self, *a, **k)])
    return apply


def _answer_altered(dep):
    """One query's answers, every arm, changed where the engine makes them."""
    eng = dep.engine
    K = dep.pool.num_classes
    eng.answers = eng.answers.copy()
    q = eng.answers.shape[1] - 5                  # a query late in the window
    eng.answers[:, q] = (eng.answers[:, q] + 1) % K
    # the reference reads the traffic's own table, not the engine's copy


def _half_left_out(monkeypatch):
    from repro.serving.scheduler import BlockFuture

    orig = BlockFuture._fill

    def fill(self, pos, *cols):
        keep = np.asarray(pos) % 2 == 0       # every other request of a block
        return orig(self, pos[keep], *(c[keep] if isinstance(c, np.ndarray) else c
                                       for c in cols))
    return lambda dep: monkeypatch.setattr(BlockFuture, "_fill", fill)


def _prediction_altered(monkeypatch):
    from repro.serving.router import PendingRoute

    orig_result, orig_step = PendingRoute.result, PendingRoute.step

    def result(self):
        res = orig_result(self)
        if self.kind == "jit" and not getattr(self, "_bench_bent", False):
            res.predictions = res.predictions.copy()
            res.predictions[0] = (res.predictions[0] + 1) % self.router.num_classes
            self._bench_bent = True
        return res

    def step(self):
        rows, preds = orig_step(self)
        if preds is not None and preds.size:
            preds = preds.copy()
            preds[0] = (preds[0] + 1) % self.router.num_classes
        return rows, preds

    def apply(dep):
        monkeypatch.setattr(PendingRoute, "result", result)
        monkeypatch.setattr(PendingRoute, "step", step)
    return apply


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "prediction_altered", "best_single_planned"])
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    make = {
        "answer_altered": lambda mp: _answer_altered,
        "half_left_out": _half_left_out,
        "prediction_altered": _prediction_altered,
        "best_single_planned": _best_single_planned,
    }[fault]
    res = _run(cell, fault=make(monkeypatch), monkeypatch=monkeypatch)
    assert not res["correct"], res["checks"]


# The drift cell at a size the CPU serves in seconds: drift every second
# of a 3 s window, so the planned arms of 4 clusters fall at 1 s and are
# restored at 2 s; the gates fire and the planner replans inside it.
DRIFT = dict(rate_qps=500.0, warmup_s=0.5, drift_period_s=1.0)
DRIFT_SEED = 2**31 + 29


def _drift_run(monkeypatch, fault=None):
    return _run("hellaswag-api-drift", fault=fault, monkeypatch=monkeypatch,
                seconds=3.0, seed=DRIFT_SEED, **DRIFT)


def _window_counters(out: str) -> dict:
    line = next(ln for ln in out.splitlines() if ln.startswith("window counters: "))
    return json.loads(line[len("window counters: "):].split(" | ")[0])


def test_sound_drift_run_is_correct_with_gates_fired_and_replans(monkeypatch, capsys):
    res = _drift_run(monkeypatch)
    counters = _window_counters(capsys.readouterr().out)
    assert res["correct"], res["checks"]
    assert res["checks"]["gate_disagreements"]["value"] == 0
    assert counters["feedback_drifts"] >= 1 and counters["batch_replans"] >= 1
    assert counters["feedback_labels"] > 0 and counters["feedback_applies"] > 0


def _first_drifted_cluster(dep):
    """The program's id of the first cluster the window's first drift hits."""
    _, _, mix = harness.load_cell("hellaswag-api-drift", BENCH)
    tr = dep.traffic(dict(mix, **DRIFT), DRIFT_SEED, 3.0)
    true_cluster = int(tr.drift[0][2][0])
    return int(dep.estimator.lookup_batch(dep.pool.centers[true_cluster][None, :])[0])


def _fold_drops_a_cluster(monkeypatch):
    """The labels of one cluster, one the first drift hits, never fold."""
    from repro.serving.feedback import FeedbackLog

    orig = FeedbackLog.record_many

    def apply(dep):
        target = _first_drifted_cluster(dep)

        def record_many(self, ids, labels):
            ids, labels = np.asarray(ids), np.asarray(labels)
            keep = np.asarray([
                rid not in self._watch
                or self._blocks[self._watch[rid][0]][0][self._watch[rid][1]] != target
                for rid in ids.tolist()], bool)
            return orig(self, ids[keep], labels[keep])
        monkeypatch.setattr(FeedbackLog, "record_many", record_many)
    return apply


def _gate_never_fires(monkeypatch):
    from repro.serving.feedback import FeedbackLog

    return lambda dep: monkeypatch.setattr(FeedbackLog, "_moved",
                                           lambda self, *a: False)


def _replan_keeps_the_stale_plan(monkeypatch):
    """Plans are keyed without the cluster's version: a drifted cluster
    goes on being served the plan of its old estimate."""
    from repro.serving.plans import PlanService

    def key(self, cid, budget):
        return (int(cid), float(budget), 0, self._cost_fp)
    return lambda dep: monkeypatch.setattr(PlanService, "_plan_key", key)


def _label_folded_twice(monkeypatch):
    from repro.serving.feedback import FeedbackLog

    orig = FeedbackLog.apply

    def apply(self):
        for buf in self._pending.values():
            buf[0] = buf[0] * 2
            buf[1] = buf[1] * 2
        return orig(self)
    return lambda dep: monkeypatch.setattr(FeedbackLog, "apply", apply)


@pytest.mark.parametrize("fault", ["fold_drops_a_cluster", "gate_never_fires",
                                   "replan_keeps_the_stale_plan",
                                   "label_folded_twice"])
def test_a_broken_feedback_loop_is_not_correct(fault, monkeypatch):
    make = {
        "fold_drops_a_cluster": _fold_drops_a_cluster,
        "gate_never_fires": _gate_never_fires,
        "replan_keeps_the_stale_plan": _replan_keeps_the_stale_plan,
        "label_folded_twice": _label_folded_twice,
    }[fault]
    res = _drift_run(monkeypatch, fault=make(monkeypatch))
    assert not res["correct"], res["checks"]


def _planner_cut(monkeypatch, keep):
    """The planner returns ``keep(result)`` in place of its SurGreedy pick."""
    import dataclasses

    from repro.core.selection import ThriftLLM

    orig_one, orig_many = ThriftLLM.select, ThriftLLM.select_many

    def cut(res):
        return dataclasses.replace(res, chosen=np.asarray(keep(res), np.int64))

    def apply(dep):
        monkeypatch.setattr(ThriftLLM, "select",
                            lambda self, *a, **k: cut(orig_one(self, *a, **k)))
        monkeypatch.setattr(ThriftLLM, "select_many",
                            lambda self, *a, **k: [cut(r) for r in orig_many(self, *a, **k)])
    return apply


def _too_few_draws(monkeypatch):
    """The planner reads xi from a 64th of Algorithm 3's draws."""
    from repro.core.selection import ThriftLLM

    orig = ThriftLLM.theta
    return lambda dep: monkeypatch.setattr(
        ThriftLLM, "theta", lambda self, *a: max(1, orig(self, *a) // 64))


@pytest.mark.parametrize("fault", ["gamma_greedy_only", "best_single_only",
                                   "xi_greedy_only", "too_few_draws"])
def test_a_planner_short_of_surgreedy_is_not_correct(fault, monkeypatch):
    make = {
        "gamma_greedy_only": lambda mp: _planner_cut(mp, lambda r: r.s2),
        "best_single_only": lambda mp: _planner_cut(mp, lambda r: [r.l_star]),
        "xi_greedy_only": lambda mp: _planner_cut(mp, lambda r: r.s1),
        "too_few_draws": _too_few_draws,
    }[fault]
    res = _drift_run(monkeypatch, fault=make(monkeypatch))
    assert not res["correct"], res["checks"]
    assert res["checks"]["plan_xi_gap"]["value"] > res["checks"]["plan_xi_gap"]["limit"]


def test_control_fails_the_drift_cell(monkeypatch):
    w, config, mix = harness.load_cell("hellaswag-api-drift", BENCH)
    monkeypatch.setattr(harness.Deployment, "warm_planner", lambda self: 0)
    harness.start_jax(1, require_tpu=False)
    rec = harness.Recorder(False, feedback=True)
    dep, tr, _ = harness.prepare(config, dict(mix, **DRIFT), DRIFT_SEED + 1, 3.0,
                                 rec=rec)
    with rec.installed():
        served = harness.serve(dep, tr, tr.n_warm, tr.n, 3.0)
    out = harness.outcomes(dep, served, rec, tr.n_warm, tr.n)
    info: dict = {}
    sound = harness.compare(dep, tr, out, tr.n_warm, tr.n, rec=rec, info=info)
    control = harness.compare(dep, tr, out, tr.n_warm, tr.n, control=True, rec=rec)
    limits = config["correct_limits"]
    assert check.verdict(sound, limits), sound
    assert info["reference_fires"] >= 1
    assert control["cost_gap"] > 1e3 * limits["cost_gap"]
    assert control["plan_xi_gap"] > limits["plan_xi_gap"]


def test_recorded_groups_carry_unpadded_shapes():
    w, config, mix = harness.load_cell("agnews-poisson", BENCH)
    mix = dict(mix, rate_qps=300.0, warmup_s=0.0)
    dep, tr, _ = harness.prepare(config, mix, 3, 0.5)
    rec = harness.Recorder(False)
    with rec.installed():
        harness.serve(dep, tr, 0, tr.n, 0.5)
    depth = dep.plan_depth()
    assert rec.groups and all(kind == "jit" for _, _, _, kind in rec.groups)
    for _, qidx, sched_T, _ in rec.groups:
        assert isinstance(sched_T, np.ndarray)
        assert sched_T.shape == (sched_T.shape[0], qidx.shape[0])
        assert sched_T.shape[0] <= depth
    assert sum(qidx.shape[0] for _, qidx, _, _ in rec.groups) == tr.n
