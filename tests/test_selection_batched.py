"""Batched planner (`sur_greedy_many` / `select_many` / `plan_many`):
bitwise equivalence with the serial plane under shared CRN seeds.

The contract under test is the PR 5 tentpole: one jitted program planning G
(p-vector, budget) groups returns exactly the chosen sets, orders, values
and spend the serial per-group `sur_greedy` produces — across shapes,
ragged affordability, padding buckets and group permutations.
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # container fallback
    from _hypolite import given, settings, strategies as st

from repro.core import (
    GroupedXiEstimator,
    ThriftLLM,
    sample_pool_responses,
    sample_pool_responses_grouped,
    sur_greedy,
    sur_greedy_many,
)
from repro.core.types import SelectionResult


def _assert_same(s: SelectionResult, m: SelectionResult):
    """Bitwise equality of everything the planner derives."""
    assert np.array_equal(s.chosen, m.chosen)
    assert s.xi_est == m.xi_est and s.cost == m.cost and s.budget == m.budget
    assert (s.s1 is None) == (m.s1 is None)
    if s.s1 is not None:
        assert np.array_equal(s.s1, m.s1) and np.array_equal(s.s2, m.s2)
        assert s.l_star == m.l_star
        assert s.xi_s1 == m.xi_s1 and s.xi_s2 == m.xi_s2
        assert s.p_star == m.p_star and s.gamma_s2 == m.gamma_s2


def _case(seed, G, L, K, budget_lo, budget_hi):
    rng = np.random.default_rng(seed)
    ps = rng.uniform(0.2, 0.98, (G, L))
    b = rng.uniform(0.05, 1.0, L)
    budgets = rng.uniform(budget_lo, budget_hi, G)
    thetas = rng.integers(120, 700, G)
    return ps, b, budgets, thetas


class TestBatchedEqualsSerial:
    @pytest.mark.parametrize(
        "seed,G,L,K",
        [
            (0, 1, 4, 2),      # single group == the serial plane
            (1, 3, 6, 3),
            (2, 8, 12, 4),     # a full group bucket
            (3, 9, 12, 4),     # ragged G (padded to the next bucket)
            (4, 5, 8, 7),
            (5, 4, 6, 19),     # big-K histogram fallback path
        ],
    )
    def test_equivalence_grid(self, seed, G, L, K):
        ps, b, budgets, thetas = _case(seed, G, L, K, 0.3, 2.5)
        key = jax.random.key(42)
        serial = [
            sur_greedy(ps[g], b, float(budgets[g]), K, key, int(thetas[g]))
            for g in range(G)
        ]
        batched = sur_greedy_many(ps, b, budgets, K, key, thetas)
        for s, m in zip(serial, batched):
            _assert_same(s, m)

    def test_ragged_affordability(self):
        """Groups whose budget affords nothing reproduce the serial early
        return, and their presence does not perturb the live groups."""
        ps, b, budgets, thetas = _case(7, 6, 8, 4, 0.3, 1.5)
        budgets[1] = 0.0                     # affords nothing
        budgets[4] = float(b.min()) * 0.5    # still nothing
        key = jax.random.key(3)
        serial = [
            sur_greedy(ps[g], b, float(budgets[g]), 4, key, int(thetas[g]))
            for g in range(6)
        ]
        batched = sur_greedy_many(ps, b, budgets, 4, key, thetas)
        for s, m in zip(serial, batched):
            _assert_same(s, m)
        assert batched[1].chosen.size == 0 and batched[1].s1 is None
        assert batched[1].xi_est == 0.25

    def test_shared_draws_are_prefix_stable(self):
        """Group g's rows of the grouped sample tensor are bitwise the
        serial draws for its own theta — the CRN sharing contract."""
        key = jax.random.key(11)
        ps = np.random.default_rng(0).uniform(0.2, 0.95, (3, 6)).astype(np.float32)
        grouped = np.asarray(
            sample_pool_responses_grouped(key, ps, 5, 512)
        )
        for g, t in enumerate((17, 256, 512)):
            one = np.asarray(sample_pool_responses(key, ps[g], 5, t))
            assert np.array_equal(one, grouped[g, :t])

    def test_grouped_estimator_padding_invariance(self):
        """xi of the same masks is bitwise identical whether a group is
        evaluated alone or stacked with larger-theta groups (padding and
        batching cannot perturb the exact credit sums)."""
        rng = np.random.default_rng(5)
        ps = rng.uniform(0.3, 0.95, (3, 6))
        thetas = np.asarray([150, 400, 611])
        key = jax.random.key(2)
        est = GroupedXiEstimator(key, ps, 4, thetas)
        masks = (rng.random((3, 5, 6)) < 0.5).astype(np.float32)
        stacked = est(masks)
        for g in range(3):
            alone = GroupedXiEstimator(key, ps[g][None], 4, thetas[g:g + 1])
            np.testing.assert_array_equal(alone(masks[g][None])[0], stacked[g])


class TestSelectMany:
    def test_select_many_matches_select_and_shares_cache(self):
        ps, b, budgets, _ = _case(9, 5, 8, 4, 0.4, 2.0)
        sel_a = ThriftLLM(b, eps=0.3, seed=1)
        sel_b = ThriftLLM(b, eps=0.3, seed=1)
        serial = [sel_a.select(ps[g], 4, float(budgets[g])) for g in range(5)]
        batched = sel_b.select_many(ps, 4, budgets)
        for s, m in zip(serial, batched):
            _assert_same(s, m)
        # the batched results are memoized under the serial keys: a serial
        # select afterwards is a pure cache hit returning the same object
        for g in range(5):
            assert sel_b.select(ps[g], 4, float(budgets[g])) is batched[g]

    def test_select_many_duplicate_pairs_build_once(self):
        ps, b, budgets, _ = _case(10, 2, 6, 3, 0.5, 1.5)
        dup = np.concatenate([ps, ps[:1]])
        dbud = np.concatenate([budgets, budgets[:1]])
        sel = ThriftLLM(b, eps=0.3)
        out = sel.select_many(dup, 3, dbud)
        assert out[0] is out[2]               # same memo entry, one build


class TestCompileBudget:
    """CompileSentinel: `_sur_greedy_scan` is compiled per (G-bucket, L,
    theta-bucket, K) — steady replanning traffic must stay in cache."""

    def test_sur_greedy_many_content_change_does_not_recompile(self):
        from repro.analysis import CompileSentinel, compile_cache_size
        from repro.core import selection as selection_mod

        G, L, K = 8, 12, 4
        thetas = np.full(G, 300)        # pin the theta bucket across runs
        b = np.random.default_rng(0).uniform(0.05, 1.0, L)
        key = jax.random.key(42)
        sentinel = CompileSentinel(
            {"plan": selection_mod._sur_greedy_scan}
        )
        rng = np.random.default_rng(1)
        sur_greedy_many(
            rng.uniform(0.2, 0.98, (G, L)), b, rng.uniform(0.3, 2.5, G),
            K, key, thetas,
        )
        # in cache (earlier tests may have warmed this bucket already, so
        # assert the absolute population, not the since-construction delta)
        assert compile_cache_size(selection_mod._sur_greedy_scan) >= 1
        sentinel.snapshot()
        for s in (2, 3, 4):
            rng = np.random.default_rng(s)
            sur_greedy_many(
                rng.uniform(0.2, 0.98, (G, L)), b,
                rng.uniform(0.3, 2.5, G), K, key, thetas,
            )
        sentinel.assert_no_new_compiles(
            detail="sur_greedy_many content change within one "
            "(G, theta) bucket"
        )

    def test_ragged_groups_share_the_warm_bucket(self):
        from repro.analysis import CompileSentinel
        from repro.core import selection as selection_mod

        L, K = 12, 4
        b = np.random.default_rng(0).uniform(0.05, 1.0, L)
        key = jax.random.key(7)
        sentinel = CompileSentinel(
            {"plan": selection_mod._sur_greedy_scan}
        )
        rng = np.random.default_rng(9)
        sur_greedy_many(
            rng.uniform(0.2, 0.98, (8, L)), b, rng.uniform(0.3, 2.5, 8),
            K, key, np.full(8, 300),
        )
        sentinel.snapshot()
        # ragged G in (5, 6, 7) pads to the same G=8 bucket: cache hits only
        for G in (5, 6, 7):
            rng = np.random.default_rng(G)
            sur_greedy_many(
                rng.uniform(0.2, 0.98, (G, L)), b,
                rng.uniform(0.3, 2.5, G), K, key, np.full(G, 300),
            )
        sentinel.assert_no_new_compiles(
            detail="ragged G padded into the warm G-bucket"
        )


class TestFusedGammaPlane:
    """PR 10: the greedy-on-gamma / l* / candidate-scoring stages moved
    into the device program — the fused plane must stay bitwise the serial
    one everywhere, including exact gamma ties and padding."""

    @pytest.mark.parametrize(
        "seed,G,L,budget_lo,budget_hi",
        [
            (20, 1, 4, 0.3, 1.0),
            (21, 3, 8, 0.2, 0.8),    # tight budgets: ragged affordability
            (22, 8, 6, 0.5, 2.5),
            (23, 9, 10, 0.3, 3.5),   # ragged G, generous budgets
            (24, 5, 12, 0.1, 0.6),
        ],
    )
    def test_equivalence_grid(self, seed, G, L, budget_lo, budget_hi):
        K = 4
        ps, b, budgets, thetas = _case(seed, G, L, K, budget_lo, budget_hi)
        key = jax.random.key(5)
        serial = [
            sur_greedy(ps[g], b, float(budgets[g]), K, key, int(thetas[g]))
            for g in range(G)
        ]
        batched = sur_greedy_many(ps, b, budgets, K, key, thetas)
        for s, m in zip(serial, batched):
            _assert_same(s, m)

    def test_exact_gamma_ties(self):
        """Duplicated (p, b) columns make every gamma-plane round an exact
        ratio tie; the device argmax must reproduce the serial p/b-then-
        first-index tie-break bit for bit."""
        rng = np.random.default_rng(30)
        G, half = 4, 5
        ps_half = rng.uniform(0.3, 0.9, (G, half))
        ps = np.concatenate([ps_half, ps_half], axis=1)
        b_half = rng.uniform(0.1, 0.8, half)
        b = np.concatenate([b_half, b_half])
        budgets = rng.uniform(0.5, 3.0, G)
        thetas = rng.integers(150, 500, G)
        key = jax.random.key(8)
        serial = [
            sur_greedy(ps[g], b, float(budgets[g]), 3, key, int(thetas[g]))
            for g in range(G)
        ]
        batched = sur_greedy_many(ps, b, budgets, 3, key, thetas)
        for s, m in zip(serial, batched):
            _assert_same(s, m)

    def test_nothing_affordable_groups_are_inert(self):
        """Zero-budget groups take the serial early return and do not
        perturb the live groups sharing their dispatch."""
        ps, b, budgets, thetas = _case(31, 5, 7, 4, 0.5, 2.0)
        budgets[0] = 0.0
        budgets[3] = float(b.min()) * 0.25
        key = jax.random.key(9)
        serial = [
            sur_greedy(ps[g], b, float(budgets[g]), 4, key, int(thetas[g]))
            for g in range(5)
        ]
        batched = sur_greedy_many(ps, b, budgets, 4, key, thetas)
        for s, m in zip(serial, batched):
            _assert_same(s, m)
        assert batched[0].s1 is None and batched[3].s1 is None

    def test_padded_bucket_invariance(self):
        """The same groups planned under group_bucket=8 (G=5 pads to 8)
        and group_bucket=64 (pads to 64) are bitwise identical — padded
        rows are inert."""
        ps, b, budgets, thetas = _case(32, 5, 9, 4, 0.3, 2.0)
        key = jax.random.key(12)
        small = sur_greedy_many(
            ps, b, budgets, 4, key, thetas, group_bucket=8
        )
        large = sur_greedy_many(
            ps, b, budgets, 4, key, thetas, group_bucket=64
        )
        for s, m in zip(small, large):
            _assert_same(s, m)

    def test_hostgamma_baseline_equivalence(self):
        """The retained PR 9 plane (host gamma/l* loop + separate final_xi
        dispatch) and the fused plane agree bitwise — the bench baseline
        measures speed, not drift."""
        from repro.core.selection import _sur_greedy_many_hostgamma

        ps, b, budgets, thetas = _case(33, 7, 8, 4, 0.3, 2.5)
        key = jax.random.key(21)
        fused = sur_greedy_many(ps, b, budgets, 4, key, thetas)
        host = _sur_greedy_many_hostgamma(ps, b, budgets, 4, key, thetas)
        for s, m in zip(host, fused):
            _assert_same(s, m)


class TestDonationSafety:
    """`donate_argnums` on the planner scan: bit-identical results, and
    the donated device buffers really are handed over (deleted)."""

    def test_donate_on_off_bit_identical(self):
        ps, b, budgets, thetas = _case(40, 6, 8, 4, 0.3, 2.0)
        key = jax.random.key(31)
        on = sur_greedy_many(ps, b, budgets, 4, key, thetas, donate=True)
        off = sur_greedy_many(ps, b, budgets, 4, key, thetas, donate=False)
        for s, m in zip(on, off):
            _assert_same(s, m)

    def test_donation_semantics_delete_usable_buffers(self):
        """The contract the `donation-contract` lint rule guards: when a
        donated input CAN alias an output, XLA deletes it and a host
        re-read raises. (Demonstrated on a minimal wrapper whose output
        shape matches — the planner/wave programs return reductions, see
        the companion test below.)"""
        import functools

        import jax.numpy as jnp

        donating = functools.partial(jax.jit, donate_argnums=(0,))(
            lambda x, y: x * 2.0 + y
        )
        x = jnp.ones((16, 16))
        y = jnp.ones((16, 16))
        out = donating(x, y)
        jax.block_until_ready(out)
        assert x.is_deleted() and not y.is_deleted()
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(x)

    def test_planner_donation_is_declarative_on_reduction_outputs(self):
        """`_sur_greedy_scan` donates only its staging tables (resp, valid,
        w): every other input stays readable after the call, the nodonate
        twin keeps all of them, and both return the same plans. Whether
        XLA reuses a donated table is the backend's choice (on jax 0.9's
        CPU backend it takes `w`), which is why callers hand the planner
        host numpy and never re-read a donated device array."""
        import jax.numpy as jnp
        from repro.core.x64 import x64

        from repro.core import selection as selection_mod
        from repro.core.mc import GroupedXiEstimator as GXE

        ps, b, budgets, thetas = _case(41, 2, 5, 3, 0.5, 1.5)
        key = jax.random.key(2)
        est = GXE(key, ps, 3, thetas)
        scr, b_p, _, _ = selection_mod._stage_groups(est, b, budgets, 8)
        outs = []
        for wrapper, kept in (
            (selection_mod._sur_greedy_scan,
             ("empty", "theta", "p", "budgets", "m")),
            (selection_mod._sur_greedy_scan_nodonate, tuple(scr)),
        ):
            with x64(), selection_mod._quiet_donation():
                dev = {k: jnp.asarray(v) for k, v in scr.items()}
                dev_b = jnp.asarray(b_p)
                out = wrapper(
                    dev["resp"], dev["valid"], dev["w"], dev["empty"],
                    dev["theta"], dev["p"], dev_b, dev["budgets"],
                    dev["m"], num_classes=3, full=True,
                )
                outs.append([np.asarray(o) for o in out])
            for name in kept:
                assert not dev[name].is_deleted(), name
                np.asarray(dev[name])
            assert not dev_b.is_deleted()
        for got, want in zip(*outs):
            np.testing.assert_array_equal(got, want)

    def test_host_scratch_survives_donation(self):
        """The serving path passes the module-level staging scratch as
        numpy: back-to-back plans reusing the same scratch buffers must
        stay correct (the jit donates its own transfer, not our arrays)."""
        ps, b, budgets, thetas = _case(42, 4, 6, 4, 0.4, 2.0)
        key = jax.random.key(13)
        first = sur_greedy_many(ps, b, budgets, 4, key, thetas)
        again = sur_greedy_many(ps, b, budgets, 4, key, thetas)
        for s, m in zip(first, again):
            _assert_same(s, m)


# ---------------------------------------------------------------------------
# Property: the batched greedy is invariant to group permutation
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=2, max_value=7),    # G
    st.integers(min_value=3, max_value=9),    # L
    st.integers(min_value=2, max_value=5),    # K
    st.integers(min_value=0, max_value=10_000),  # data seed
    st.integers(min_value=0, max_value=10_000),  # permutation seed
)
def test_group_permutation_invariance(G, L, K, seed, perm_seed):
    ps, b, budgets, thetas = _case(seed, G, L, K, 0.2, 2.0)
    key = jax.random.key(17)
    base = sur_greedy_many(ps, b, budgets, K, key, thetas)
    perm = np.random.default_rng(perm_seed).permutation(G)
    permuted = sur_greedy_many(
        ps[perm], b, budgets[perm], K, key, thetas[perm]
    )
    for i, g in enumerate(perm):
        _assert_same(base[g], permuted[i])
