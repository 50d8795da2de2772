"""The feedback loop's reference (``lib/fold.py``) on small cases: the
Wilson interval, the fold, the gate, the replay of recorded boundaries and
the floor that planned sets are held to."""
import numpy as np
import pytest

from bench.lib import fold, planref


def test_wilson_interval_of_its_textbook_case():
    # 50 successes in 100 at 95 %: 0.5 -+ 1.96 sqrt(0.25/100 + 1.96^2/4e4) / (1 + 1.96^2/100)
    lo, hi = fold.wilson(np.array([0.5, 0.3]), np.array([100.0, 0.0]), 0.05)
    assert lo[0] == pytest.approx(0.40383, abs=1e-5)
    assert hi[0] == pytest.approx(0.59617, abs=1e-5)
    assert (lo[1], hi[1]) == (0.0, 1.0)          # never observed: vacuous


def test_fold_is_the_streaming_mean_and_keeps_unasked_arms():
    p, n = fold.fold(np.array([0.8, 0.6]), np.array([250.0, 250.0]),
                     np.array([10.0, 0.0]), np.array([50.0, 0.0]))
    assert p[0] == pytest.approx((200 + 10) / 300) and p[1] == 0.6
    assert list(n) == [300.0, 250.0]


def test_gate_fires_only_on_an_observed_arm_whose_interval_left():
    p_s, n_s = np.array([[0.8, 0.8]]), np.array([[250.0, 250.0]])
    moved = np.array([[0.55, 0.8]])
    n = np.array([[400.0, 250.0]])
    assert fold.drifted(p_s, n_s, moved, n, np.array([[True, False]]), 0.05)[0]
    assert not fold.drifted(p_s, n_s, moved, n, np.array([[False, True]]), 0.05)[0]
    assert not fold.drifted(p_s, n_s, np.array([[0.78, 0.8]]), n,
                            np.array([[True, True]]), 0.05)[0]


def _one_cluster_run(drop=False):
    """Two routed groups of one cluster; every arm of the second answers
    wrong. Labels of the first group come back before fold 0, of the
    second before fold 1."""
    L, K, N = 3, 4, 400
    labels = np.zeros(N, np.int64)
    answers = np.zeros((L, N), np.int64)
    answers[:, 200:] = 1                          # all wrong in the second half
    rows = np.arange(N)
    group = (rows >= 200).astype(np.int64)
    cluster = np.zeros(N, np.int64)
    arm_set = np.ones((N, L), bool)
    p0 = np.array([[0.9, 0.9, 0.9]])
    n0 = np.full((1, L), 50.0)
    second = rows[200:] if not drop else rows[200:201]
    boundaries = [(1, set()), (2, {0})]
    return fold.replay(p0, n0, 0.05, rows, group, cluster, arm_set, answers,
                       labels, np.array([1e-6, 2e-6, 3e-6]), K, boundaries,
                       [(0, rows[:200]), (1, second)], np.full(N, -1, np.int64))


def test_replay_fires_where_the_labels_move_the_estimate():
    rp, disagree = _one_cluster_run()
    assert rp.fired_log == [set(), {0}] and disagree == 0
    assert rp.fires == 1 and len(rp.snaps_p) == 2
    assert rp.snaps_p[1][0] < 0.7           # the second half answered wrong
    # the program that misses the second fold's labels is out of step
    _, disagree = _one_cluster_run(drop=True)
    assert disagree == 1


def test_plan_gap_reads_nought_on_the_floor_and_more_on_a_worse_set():
    rng = np.random.default_rng(4)
    p = rng.uniform(0.3, 0.95, 12)
    costs = np.geomspace(4e-7, 2e-4, 12)
    xi = planref.Xi(p, 4, np.random.default_rng([planref.DRAWS_SEED, 0]))
    own = planref.sur_greedy(xi, costs, 1e-4)         # the same draws' SurGreedy
    snap, budgets = np.zeros(3, np.int64), np.full(3, 1e-4)
    gap = fold.plan_gap([p], costs, 4, snap, budgets, np.stack([own] * 3))
    assert gap == 0.0
    cheap = np.zeros(12, bool)
    cheap[0] = True
    gap = fold.plan_gap([p], costs, 4, snap, budgets, np.stack([own, own, cheap]))
    assert 0.05 < gap <= xi(own[None, :])[0] - xi(cheap[None, :])[0]
