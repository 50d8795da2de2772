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


def test_xi_is_the_share_of_draws_whose_vote_names_the_truth():
    # the products over one-hot answers against a plain loop over the draws
    rng = np.random.default_rng(12)
    p = rng.uniform(0.2, 0.99, 6)
    xi = planref.Xi(p, 3, np.random.default_rng(5), samples=64)
    w, K = xi.w, 3
    masks = rng.random((7, 6)) < 0.5
    want = []
    for m in masks:
        credit = 0.0
        for s in range(64):
            names = np.argmax(xi.onehot_t[:, :, s], axis=0)      # each arm's answer
            bel = np.full(K, xi.empty)
            for k in range(K):
                votes = m & (names == k)
                if votes.any():
                    bel[k] = w[votes].sum()
            top = bel == bel.max()
            credit += top[0] / top.sum()
        want.append(credit / 64)
    assert np.allclose(xi(masks), want, rtol=0, atol=1e-12)


def test_grown_sets_read_as_their_masks_do():
    rng = np.random.default_rng(5)
    for t in range(6):
        p = rng.uniform(0.05, 1.0, 12)
        p[rng.integers(12, size=3)] = p[0]            # equal weights: exact ties
        xi = planref.Xi(p, K, np.random.default_rng(t), 4096)
        chosen = rng.random(12) < 0.4
        cand = np.flatnonzero(~chosen)
        masks = np.repeat(chosen[None], cand.size + 1, axis=0)
        masks[np.arange(1, cand.size + 1), cand] = True
        assert np.array_equal(xi.grown(chosen, cand), xi.per_draw(masks))


def test_greedy_ends_without_theta_is_algorithm_1():
    rng = np.random.default_rng(6)
    costs = np.geomspace(4e-7, 2e-4, 12)
    for t in range(4):
        xi = _xi(rng.uniform(0.3, 0.95, 12))
        for budget in (1e-5, 5e-5, 1e-4, 1e-3):
            ends = planref.greedy_ends(xi, costs, budget)
            assert ends.shape[0] == 1
            assert np.array_equal(ends[0], planref._greedy(xi.p, costs, budget, xi))


def test_greedy_ends_branch_on_arms_the_planners_draws_cannot_tell_apart():
    # arms 1 and 2 are alike and only one fits beside arm 0: Algorithm 1
    # takes the first, a planner reading xi from its own draws either
    p = [0.9, 0.7, 0.7, 0.5]
    costs = np.array([1e-5, 2e-5, 2e-5, 1e-3])
    xi = _xi(p)
    assert planref.greedy_ends(xi, costs, 3.5e-5).tolist() == [[True, True, False, False]]
    ends = planref.greedy_ends(xi, costs, 3.5e-5, theta_n=8421)
    assert sorted(map(tuple, ends.tolist())) == [(True, False, True, False),
                                                  (True, True, False, False)]


def test_theta_is_algorithm_3s_sample_count():
    costs = np.array([1e-6, 1e-5, 1e-4])
    p = np.array([0.5, 0.8, 0.99])
    want = lambda ps: int(np.ceil(8.2 / (0.01 * ps) * np.log(2 * 9 / 0.01)))  # noqa: E731
    assert planref.theta(p, costs, 2e-5) == want(0.8)
    assert planref.theta(p, costs, 1e-3) == want(0.99)
    assert planref.theta(p, costs, 1e-7) == want(1.0)


def test_surgreedys_floor_lies_at_most_at_its_result_and_above_a_worse_set():
    rng = np.random.default_rng(7)
    p = rng.uniform(0.3, 0.95, 12)
    costs = np.geomspace(4e-7, 2e-4, 12)
    xi = _xi(p)
    for budget in (1e-5, 1e-4, 1e-3):
        n = planref.theta(p, costs, budget)
        own = planref.sur_greedy(xi, costs, budget)
        assert planref.sur_greedy_floor(xi, costs, budget, n) <= xi(own[None])[0]
    n = planref.theta(p, costs, 1e-4)
    single = planref.best_single(xi, costs, 1e-4)
    assert planref.sur_greedy_floor(xi, costs, 1e-4, n) > xi(single[None])[0] + 0.05
    nothing = xi(np.zeros((1, 12), bool))[0]
    assert planref.sur_greedy_floor(xi, costs, 1e-8, n) == nothing
