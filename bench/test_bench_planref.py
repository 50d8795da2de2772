"""The reference planner: its correctness probability on sets whose value
is known, and SurGreedy's choice against the budget and its candidates."""
import numpy as np
import pytest

from bench.lib import planref

K = 4


def _xi(p, samples=planref.SAMPLES):
    return planref.Xi(np.asarray(p, float), K, np.random.default_rng(0), samples)


def test_empty_set_scores_one_in_k():
    xi = _xi([0.7, 0.8, 0.9])
    assert xi(np.zeros((1, 3), bool))[0] == pytest.approx(1.0 / K, abs=1e-12)


@pytest.mark.parametrize("arm", [0, 1, 2])
def test_one_arm_scores_its_own_accuracy(arm):
    p = [0.55, 0.8, 0.93]
    xi = _xi(p, samples=1 << 16)
    mask = np.zeros((1, 3), bool)
    mask[0, arm] = True
    assert xi(mask)[0] == pytest.approx(p[arm], abs=0.01)


def test_three_equal_arms_score_the_majority_vote():
    # three arms of accuracy q: right if two or three are right, or if all
    # three differ (the tie of three, one of which is true, counts 1/3)
    q = 0.6
    xi = _xi([q, q, q], samples=1 << 17)
    two = 3 * q**2 * (1 - q) + q**3
    # one right, two wrong and differing from each other (K-1=3 wrong classes)
    split = 3 * q * (1 - q) ** 2 * (2 / 3)
    assert xi(np.ones((1, 3), bool))[0] == pytest.approx(two + split / 3, abs=0.01)


def test_sur_greedy_keeps_to_the_budget_and_beats_its_candidates():
    rng = np.random.default_rng(3)
    p = rng.uniform(0.4, 0.95, 8)
    costs = np.geomspace(1e-6, 1e-4, 8)
    xi = _xi(p)
    for budget in (5e-6, 3e-5, 2e-4):
        got = planref.sur_greedy(xi, costs, budget)
        assert costs[got].sum() <= budget * (1 + 1e-12)
        single = planref.best_single(xi, costs, budget)
        assert xi(got[None])[0] >= xi(single[None])[0]


def test_plan_gap_reads_nought_on_the_references_own_sets():
    p = np.random.default_rng(4).uniform(0.4, 0.95, (2, 6))
    costs = np.geomspace(1e-6, 1e-4, 6)
    xis = planref.xis_for(p, K)
    pairs = np.array([[0, 5e-5], [1, 5e-5], [1, 1e-3]])
    sets = np.stack([planref.sur_greedy(xis[int(c)], costs, b) for c, b in pairs])
    assert planref.plan_gap(xis, costs, pairs, sets) == 0.0
    worse = sets.copy()
    worse[2] = planref.best_single(xis[1], costs, 1e-3)
    assert planref.plan_gap(xis, costs, pairs, worse) > 0.0
