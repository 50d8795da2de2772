"""The comparison that decides ``correct``: the plain reference agrees
with the paper's loop, a sound run comes out correct, and the control and
each fault of the timed path (the planner's included) come out not
correct. Runs the harness on the CPU at a small rate (the look for a chip
is skipped)."""
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


def _run(cell, fault=None):
    w, config, mix = harness.load_cell(cell, BENCH)
    mix = dict(mix, rate_qps=300.0, warmup_s=0.3)
    devices = harness.start_jax(w["chips"], require_tpu=False)
    return harness.run_cell(w, config, mix, 2**31 + 11, 0.8, False,
                            time.monotonic(), devices, BENCH,
                            require_tpu=False, fault=fault)


CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(cell)
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
    res = _run(cell, fault=make(monkeypatch))
    assert not res["correct"], res["checks"]


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
