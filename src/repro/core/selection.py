"""LLM ensemble selection: GreedyLLM (Alg. 1), SurGreedyLLM (Alg. 2) and the
adaptive ThriftLLM loop (Alg. 3).

Two planes with bit-identical outputs:

* the **serial** plane (:func:`sur_greedy`) — numpy control flow, one
  device dispatch per greedy round through the grouped CRN estimator;
* the **batched** plane (:func:`sur_greedy_many`) — G (p-vector, budget)
  groups planned by ONE jitted program (:func:`_sur_greedy_scan`): a
  ``lax.while`` over greedy rounds whose body evaluates every group's
  masked candidate expansion simultaneously over stacked ``(G, theta, L)``
  CRN response samples.

Both planes evaluate xi through the same bit-stable cores in
``repro.core.mc`` and run the same IEEE-f64 round logic (affordability,
gain/cost ratios, the Alg. 1 p/b tie-break), so under a shared CRN seed the
batched planner returns exactly the serial chosen sets, orders, values and
spend — the contract ``tests/test_selection_batched.py`` pins bitwise.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from .x64 import x64

from .belief import (
    aggregate_log_beliefs,
    empty_log_belief,
    log_weight,
    predict_from_beliefs,
    top2_beliefs,
)
from .correctness import gamma
from .mc import (
    GroupedXiEstimator,
    _marginal_xi_core,
    _tables_xi_core,
    bucket_size,
    theta_for,
)
from .types import InvocationResult, SelectionResult, clip_probs

# Continue invoking on near-ties so Prop. 4 (prediction equality) holds
# deterministically; costs at most the paper's condition, never more than S*.
STOP_MARGIN = 1e-9
RATIO_TIE_RTOL = 1e-9


def greedy(
    p: np.ndarray,
    b: np.ndarray,
    budget: float,
    value_batch_fn: Callable[[np.ndarray], np.ndarray],
    empty_value: float,
) -> Tuple[List[int], float]:
    """GreedyLLM (Algorithm 1) on an arbitrary set function.

    Each iteration evaluates *all* affordable candidates in one batched call
    and adds the arm with the best marginal-gain / cost ratio; ties broken by
    the p/b ratio (Alg. 1 line 4). Returns (chosen order, final value).
    """
    p = np.asarray(p, np.float64)
    b = np.asarray(b, np.float64)
    L = p.size
    chosen: List[int] = []
    chosen_mask = np.zeros(L, np.float32)
    cand_buf = np.empty((L, L), np.float32)   # reused across rounds
    in_pool = np.ones(L, bool)
    spent = 0.0
    current = float(empty_value)

    while True:
        afford = np.flatnonzero(in_pool & (b <= budget - spent + 1e-15))
        if afford.size == 0:
            break
        cand = cand_buf[: afford.size]
        cand[:] = chosen_mask
        cand[np.arange(afford.size), afford] = 1.0
        vals = np.asarray(value_batch_fn(cand), np.float64)
        ratios = (vals - current) / b[afford]
        best = float(np.max(ratios))
        tied = np.flatnonzero(np.isclose(ratios, best, rtol=RATIO_TIE_RTOL, atol=1e-15))
        if tied.size > 1:  # tie-break by success-prob / cost ratio
            ti = int(tied[np.argmax(p[afford[tied]] / b[afford[tied]])])
        else:
            ti = int(tied[0])
        pick = int(afford[ti])
        chosen.append(pick)
        chosen_mask[pick] = 1.0
        in_pool[pick] = False
        spent += b[pick]
        current = float(vals[ti])                 # vals aligned with afford
    return chosen, current


def gamma_value_batch(p: np.ndarray) -> Callable[[np.ndarray], np.ndarray]:
    """Batched closed-form gamma over candidate masks."""
    log1m = np.log1p(-clip_probs(p))

    def fn(masks: np.ndarray) -> np.ndarray:
        return 1.0 - np.exp(masks @ log1m)

    return fn


def _greedy_gamma(
    p: np.ndarray, b: np.ndarray, budget: float
) -> Tuple[List[int], float]:
    """Greedy-on-gamma (Alg. 1 on the closed-form gamma), serial plane.

    Carries the chosen set's survival product ``q = prod(1 - p_l)`` instead
    of re-exponentiating mask sums: each round's candidate values are
    ``1 - q * m`` with the per-arm factors ``m = exp(log1p(-p))`` computed
    once up front.  The loop body is then pure IEEE-f64 multiply/subtract —
    no transcendentals — so :func:`_sur_greedy_scan_core` can run the exact
    same statements on device and bit-match this function round for round.
    Control flow (tie window, p/b tie-break) mirrors :func:`_greedy_xi`.
    """
    p = np.asarray(clip_probs(p), np.float64)
    b = np.asarray(b, np.float64)
    L = p.size
    m = np.exp(np.log1p(-p))                  # per-arm survival factor
    in_pool = np.ones(L, bool)
    q = 1.0                                   # survival of the chosen set
    spent = 0.0
    current = 0.0                             # gamma(empty) = 0
    chosen: List[int] = []
    while True:
        afford = in_pool & (b <= budget - spent + 1e-15)
        if not afford.any():
            break
        vals = 1.0 - q * m                    # gamma(chosen ∪ {l}) for all l
        ratios = np.where(afford, (vals - current) / b, -np.inf)
        best = ratios.max()
        tied = afford & (
            (ratios == best)
            | (np.abs(ratios - best) <= 1e-15 + RATIO_TIE_RTOL * abs(best))
        )
        pb = np.where(tied, p / b, -np.inf)
        pick = int(np.argmax(pb))
        chosen.append(pick)
        in_pool[pick] = False
        spent += float(b[pick])
        current = float(vals[pick])
        q = q * float(m[pick])
    return chosen, current


def _greedy_xi(
    p: np.ndarray, b: np.ndarray, budget: float, est: GroupedXiEstimator,
    group: int = 0,
) -> Tuple[List[int], float, np.ndarray, np.ndarray]:
    """Greedy-on-xi (Alg. 1 specialized to the CRN estimator), serial plane.

    Identical control flow to :func:`greedy`, but marginal gains come from
    the estimator's incremental base+candidate evaluation
    (:meth:`GroupedXiEstimator.marginal`): the chosen set's belief table is
    carried across rounds in pick order and each round extends it by every
    candidate arm in one dispatch. :func:`_sur_greedy_scan` runs this exact
    loop, on the same evaluator, inside one jitted program — keeping both
    planes on the same arithmetic is what makes them bit-identical.
    """
    K = est.num_classes
    L = int(p.size)
    T = est.responses.shape[1]
    resp = est.responses[group]
    w32 = est.log_weights[group]
    base_raw = np.zeros((1, T, K), np.float32)
    base_cnt = np.zeros((1, T, K), np.int32)
    in_pool = np.ones(L, bool)
    spent = 0.0
    current = 1.0 / K
    chosen: List[int] = []
    while True:
        afford = in_pool & (b <= budget - spent + 1e-15)
        if not afford.any():
            break
        vals = est.marginal(base_raw, base_cnt)[group]        # (L,) f64
        ratios = np.where(afford, (vals - current) / b, -np.inf)
        best = ratios.max()
        tied = afford & (
            (ratios == best)
            | (np.abs(ratios - best) <= 1e-15 + RATIO_TIE_RTOL * abs(best))
        )
        pb = np.where(tied, p / b, -np.inf)
        pick = int(np.argmax(pb))
        chosen.append(pick)
        in_pool[pick] = False
        spent += float(b[pick])
        current = float(vals[pick])
        col = resp[:, pick]
        rows = np.flatnonzero(col >= 0)
        base_raw[0, rows, col[rows]] += w32[pick]
        base_cnt[0, rows, col[rows]] += 1
    return chosen, current, base_raw, base_cnt


def _assemble_result(
    p: np.ndarray, b: np.ndarray, budget: float, l_star: int,
    s1: Sequence[int], s2: Sequence[int], xi_vals: np.ndarray,
) -> SelectionResult:
    """Shared Alg. 2 epilogue: argmax of the three candidates + Theorem 3
    diagnostics (used by both the serial and the batched plane)."""
    cands = [
        np.asarray([l_star]), np.asarray(s1, np.int64), np.asarray(s2, np.int64)
    ]
    pick = int(np.argmax(xi_vals))
    chosen = cands[pick]
    return SelectionResult(
        chosen=chosen,
        xi_est=float(xi_vals[pick]),
        cost=float(b[chosen].sum()) if chosen.size else 0.0,
        budget=budget,
        s1=cands[1],
        s2=cands[2],
        l_star=l_star,
        xi_s1=float(xi_vals[1]),
        xi_s2=float(xi_vals[2]),
        p_star=float(p[l_star]),
        gamma_s2=gamma(p[np.asarray(s2, np.int64)]) if len(s2) else 0.0,
    )


def sur_greedy(
    p: np.ndarray,
    b: np.ndarray,
    budget: float,
    num_classes: int,
    key: jax.Array,
    theta: int,
    p_all: Optional[np.ndarray] = None,
    use_kernel: bool = False,
) -> SelectionResult:
    """SurGreedyLLM (Algorithm 2) with CRN Monte-Carlo xi estimation.

    The serial reference plane of the planner: one group, host-side greedy
    rounds, one device dispatch per round. :func:`sur_greedy_many` is the
    batched plane; under the same ``key`` it bit-matches this function
    group by group.

    Returns the best of {best affordable single arm, greedy-on-xi,
    greedy-on-gamma} together with the Theorem 3 diagnostics.
    """
    p = clip_probs(p)
    b = np.asarray(b, np.float64)
    K = int(num_classes)

    afford = np.flatnonzero(b <= budget + 1e-15)
    if afford.size == 0:
        return SelectionResult(
            chosen=np.zeros(0, np.int64), xi_est=1.0 / K, cost=0.0, budget=budget
        )
    est = GroupedXiEstimator(
        key, p[None, :], K, np.asarray([theta]), p_all=p_all,
        use_kernel=use_kernel,
    )
    l_star = int(afford[np.argmax(p[afford])])

    s1, _, s1_raw, s1_cnt = _greedy_xi(p, b, budget, est)
    s2, _ = _greedy_gamma(p, b, budget)

    # Evaluate the three candidates with the *same* CRN draws.
    xi_vals = est.final_xi([l_star], [s1], [s2], s1_raw, s1_cnt)[0]
    return _assemble_result(p, b, budget, l_star, s1, s2, xi_vals)


# ---------------------------------------------------------------------------
# The batched planner: G (p-vector, budget) groups in one jitted program
# ---------------------------------------------------------------------------


def _sur_greedy_scan_core(
    resp_t: jnp.ndarray,      # (G, L, T) int32, -1 past each group's theta
    valid: jnp.ndarray,       # (G, T) f32 0/1 draw mask
    log_weights: jnp.ndarray, # (G, L) f32
    empty: jnp.ndarray,       # (G,) f32
    theta: jnp.ndarray,       # (G,) f64
    p: jnp.ndarray,           # (G, L) f64 clipped success probs
    b: jnp.ndarray,           # (G, L) f64 pool costs
    budgets: jnp.ndarray,     # (G,) f64
    m: jnp.ndarray,           # (G, L) f64 survival factors exp(log1p(-p))
    *,
    num_classes: int,
    full: bool = True,
):
    """The whole Alg. 2 planner for all G groups as one device program.

    Four fused phases:

    1. **greedy-on-xi** — a ``lax.while`` whose rounds evaluate the masked
       candidate expansion of *every* group simultaneously
       (`_marginal_xi_core` over the stacked CRN draws), then run Alg. 1's
       round logic — affordability, gain/cost ratios, the near-tie window
       and the p/b tie-break — as f64 elementwise ops that mirror
       :func:`_greedy_xi`'s numpy statements one for one;
    2. **greedy-on-gamma** — a second ``lax.while`` mirroring
       :func:`_greedy_gamma` (survival-product carry, pure multiply /
       subtract — the serial plane precomputes the same ``m`` factors so
       neither plane exponentiates inside the loop);
    3. **l***— the best affordable single arm, a masked first-max argmax
       identical to the serial compressed ``afford[argmax(p[afford])]``;
    4. **candidate scoring** — the l*/s1/s2 belief tables accumulated in
       ascending arm order (the exact f32 operand sequence of
       :meth:`GroupedXiEstimator._accumulate`) and scored by
       :func:`_tables_xi_core` in-program: ``final_xi`` without leaving
       the device.

    Groups whose affordable set empties freeze in place; padded groups
    (budget < 0) never pick and stay inert. Runs under ``repro.core.x64.x64``.

    With ``full=False`` only phase 1 runs and the return is the PR 9
    planner surface ``(picks, npick, value, spent, base_raw, base_cnt)``
    (kept as the bench baseline / reference plane). With ``full=True``
    the return is ``(picks (G, L) int32 in pick order (-1 pad),
    npick (G,), g_picks (G, L), g_npick (G,), l_star (G,) int32,
    xi_vals (G, 3) f64)``.
    """
    G, L, T = resp_t.shape
    K = num_classes
    arange_l = jnp.arange(L, dtype=jnp.int32)

    def cond(st):
        return st["alive"].any()

    def body(st):
        afford = st["in_pool"] & (
            b <= budgets[:, None] - st["spent"][:, None] + 1e-15
        )
        has = afford.any(axis=1)
        return jax.lax.cond(
            has.any(),
            lambda: _round(st, afford, has),
            lambda: dict(st, alive=has),   # every group done: freeze
        )

    def _round(st, afford, has):
        vals = _marginal_xi_core(
            resp_t, st["base_raw"], st["base_cnt"], log_weights, empty,
            valid, theta, K,
        )                                                     # (G, L) f64
        ratios = jnp.where(afford, (vals - st["current"][:, None]) / b, -jnp.inf)
        best = jnp.max(ratios, axis=1)
        tied = afford & (
            (ratios == best[:, None])
            | (jnp.abs(ratios - best[:, None])
               <= 1e-15 + RATIO_TIE_RTOL * jnp.abs(best[:, None]))
        )
        pb = jnp.where(tied, p / b, -jnp.inf)
        pick = jnp.argmax(pb, axis=1).astype(jnp.int32)       # first max
        oh_pick = arange_l[None, :] == pick[:, None]
        upd = has[:, None] & oh_pick
        b_pick = jnp.take_along_axis(b, pick[:, None].astype(jnp.int64), 1)[:, 0]
        v_pick = jnp.take_along_axis(vals, pick[:, None].astype(jnp.int64), 1)[:, 0]
        w_pick = jnp.take_along_axis(
            log_weights, pick[:, None].astype(jnp.int64), 1
        )[:, 0]
        resp_pick = jnp.take_along_axis(
            resp_t, pick[:, None, None].astype(jnp.int64), 1
        )[:, 0, :]                                            # (G, T)
        oh_resp = resp_pick[..., None] == jnp.arange(K, dtype=resp_t.dtype)
        grow = has[:, None, None] & oh_resp                   # padded rows: -1
        return {
            "in_pool": st["in_pool"] & ~upd,
            "spent": jnp.where(has, st["spent"] + b_pick, st["spent"]),
            "current": jnp.where(has, v_pick, st["current"]),
            "base_raw": jnp.where(
                grow, st["base_raw"] + w_pick[:, None, None], st["base_raw"]
            ),
            "base_cnt": st["base_cnt"] + jnp.where(grow, 1, 0).astype(jnp.int32),
            "picks": jnp.where(
                has[:, None] & (arange_l[None, :] == st["npick"][:, None]),
                pick[:, None], st["picks"],
            ),
            "npick": st["npick"] + has.astype(jnp.int32),
            "alive": has,
        }

    init = {
        "in_pool": jnp.ones((G, L), bool),
        "spent": jnp.zeros(G, jnp.float64),
        "current": jnp.full(G, 1.0 / K, jnp.float64),
        "base_raw": jnp.zeros((G, T, K), jnp.float32),
        "base_cnt": jnp.zeros((G, T, K), jnp.int32),
        "picks": jnp.full((G, L), -1, jnp.int32),
        "npick": jnp.zeros(G, jnp.int32),
        "alive": jnp.ones(G, bool),
    }
    st = jax.lax.while_loop(cond, body, init)
    if not full:
        return (st["picks"], st["npick"], st["current"], st["spent"],
                st["base_raw"], st["base_cnt"])

    # -- phase 2: greedy-on-gamma (mirrors `_greedy_gamma` statement for
    # statement; the survival-product carry keeps the loop transcendental-
    # free, so both planes run identical IEEE multiply/subtract chains) --
    def gcond(st2):
        return st2["alive"].any()

    def gbody(st2):
        afford = st2["in_pool"] & (
            b <= budgets[:, None] - st2["spent"][:, None] + 1e-15
        )
        has = afford.any(axis=1)
        vals = 1.0 - st2["q"][:, None] * m                    # (G, L) f64
        ratios = jnp.where(
            afford, (vals - st2["current"][:, None]) / b, -jnp.inf
        )
        best = jnp.max(ratios, axis=1)
        tied = afford & (
            (ratios == best[:, None])
            | (jnp.abs(ratios - best[:, None])
               <= 1e-15 + RATIO_TIE_RTOL * jnp.abs(best[:, None]))
        )
        pb = jnp.where(tied, p / b, -jnp.inf)
        pick = jnp.argmax(pb, axis=1).astype(jnp.int32)       # first max
        oh_pick = arange_l[None, :] == pick[:, None]
        upd = has[:, None] & oh_pick
        b_pick = jnp.take_along_axis(
            b, pick[:, None].astype(jnp.int64), 1
        )[:, 0]
        v_pick = jnp.take_along_axis(
            vals, pick[:, None].astype(jnp.int64), 1
        )[:, 0]
        m_pick = jnp.take_along_axis(
            m, pick[:, None].astype(jnp.int64), 1
        )[:, 0]
        return {
            "in_pool": st2["in_pool"] & ~upd,
            "spent": jnp.where(has, st2["spent"] + b_pick, st2["spent"]),
            "current": jnp.where(has, v_pick, st2["current"]),
            "q": jnp.where(has, st2["q"] * m_pick, st2["q"]),
            "picks": jnp.where(
                has[:, None] & (arange_l[None, :] == st2["npick"][:, None]),
                pick[:, None], st2["picks"],
            ),
            "npick": st2["npick"] + has.astype(jnp.int32),
            "alive": has,
        }

    ginit = {
        "in_pool": jnp.ones((G, L), bool),
        "spent": jnp.zeros(G, jnp.float64),
        "current": jnp.zeros(G, jnp.float64),
        "q": jnp.ones(G, jnp.float64),
        "picks": jnp.full((G, L), -1, jnp.int32),
        "npick": jnp.zeros(G, jnp.int32),
        "alive": jnp.ones(G, bool),
    }
    st2 = jax.lax.while_loop(gcond, gbody, ginit)

    # -- phase 3: l* — first-max argmax over the affordable arms, the
    # device form of the serial `afford[argmax(p[afford])]` (non-afforded
    # arms at -inf lose to any affordable one; padded groups afford
    # nothing and resolve to arm 0, discarded by the caller) --
    afford0 = b <= budgets[:, None] + 1e-15
    l_star = jnp.argmax(
        jnp.where(afford0, p, -jnp.inf), axis=1
    ).astype(jnp.int32)

    # -- phase 4: Alg. 2 candidate scoring in-program. The l* and s2
    # belief tables are folded in ascending arm order — one f32 add per
    # draw per arm, the same operand sequence as
    # `GroupedXiEstimator._accumulate` — and scored by the same
    # `_tables_xi_core` the host path jits, so xi comes back bit-identical
    # to `est.final_xi(...)` without a host round-trip. --
    arange_k = jnp.arange(K, dtype=resp_t.dtype)
    resp_l = jnp.take_along_axis(
        resp_t, l_star[:, None, None].astype(jnp.int64), 1
    )[:, 0, :]                                                # (G, T)
    w_l = jnp.take_along_axis(
        log_weights, l_star[:, None].astype(jnp.int64), 1
    )                                                         # (G, 1)
    oh_l = resp_l[..., None] == arange_k                      # (G, T, K)
    raw_star = jnp.where(oh_l, w_l[:, :, None], jnp.float32(0.0))
    cnt_star = oh_l.astype(jnp.int32)

    chosen2 = ~st2["in_pool"]                                 # the s2 set

    def fold(l, carry):
        raw, cnt = carry
        sel = jax.lax.dynamic_index_in_dim(
            chosen2, l, axis=1, keepdims=False
        )                                                     # (G,)
        col = jax.lax.dynamic_index_in_dim(
            resp_t, l, axis=1, keepdims=False
        )                                                     # (G, T)
        w_arm = jax.lax.dynamic_index_in_dim(
            log_weights, l, axis=1, keepdims=False
        )                                                     # (G,)
        add = sel[:, None, None] & (col[..., None] == arange_k)
        raw = jnp.where(add, raw + w_arm[:, None, None], raw)
        cnt = cnt + jnp.where(add, 1, 0).astype(jnp.int32)
        return (raw, cnt)

    raw_s2, cnt_s2 = jax.lax.fori_loop(
        0, L, fold,
        (jnp.zeros((G, T, K), jnp.float32), jnp.zeros((G, T, K), jnp.int32)),
    )

    raw3 = jnp.stack([raw_star, st["base_raw"], raw_s2], axis=1)
    cnt3 = jnp.stack([cnt_star, st["base_cnt"], cnt_s2], axis=1)
    xi_vals = _tables_xi_core(raw3, cnt3, empty, valid, theta, K)

    return (st["picks"], st["npick"], st2["picks"], st2["npick"],
            l_star, xi_vals)


@contextlib.contextmanager
def _quiet_donation():
    """Donation is declarative — XLA aliases what it can and (on backends/
    shapes where an input can't be reused) warns once at compile time about
    the rest. The contract we assert is the caller-side one ("this buffer
    is dead after the call"), so the partial-use warning is expected noise;
    dispatch seams of donating wrappers run under this context."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


# Donating wrapper (the serving default) and its no-donation twin. The
# donated positions are the staged response/valid/weight tables: every
# caller in the tree stages them from host numpy (jit transfers a fresh
# device copy and donates *that* copy, never the host buffer), or hands
# over throwaway device arrays — after the call the argument is dead, so
# XLA may reuse its memory for loop carries and outputs. Donation changes
# buffer lifetimes only, never arithmetic: on/off is bit-identical, and
# each wrapper owns one compile per bucket (CompileSentinel-clean).
_sur_greedy_scan = functools.partial(
    jax.jit, static_argnames=("num_classes", "full"), donate_argnums=(0, 1, 2),
)(_sur_greedy_scan_core)

_sur_greedy_scan_nodonate = functools.partial(
    jax.jit, static_argnames=("num_classes", "full"),
)(_sur_greedy_scan_core)


# Reusable staging buffers for the batched planner, keyed by padded shape
# (Gp, L, T): warm replans hit the same compile bucket over and over, so
# re-allocating ~13 MB of padded tables per call is pure churn. The scratch
# is *host numpy* — the jit transfers a fresh device copy per call (and the
# donating wrapper donates that copy, never these buffers), so reuse is
# safe even with donation on. Planning is control-plane work serialized by
# the PlanService; the scratch is not thread-safe by itself.
_PLAN_SCRATCH: dict = {}


def _plan_scratch(Gp: int, L: int, T: int) -> dict:
    key = (Gp, L, T)
    scr = _PLAN_SCRATCH.get(key)
    if scr is None:
        scr = {
            "resp": np.empty((Gp, L, T), np.int32),
            "valid": np.empty((Gp, T), np.float32),
            "w": np.empty((Gp, L), np.float32),
            "empty": np.empty(Gp, np.float32),
            "theta": np.empty(Gp, np.float64),
            "p": np.empty((Gp, L), np.float64),
            "m": np.empty((Gp, L), np.float64),
            "budgets": np.empty(Gp, np.float64),
        }
        _PLAN_SCRATCH[key] = scr
    return scr


def _stage_groups(est: GroupedXiEstimator, b: np.ndarray,
                  budgets_live: np.ndarray, group_bucket: int):
    """Fill the bucket-keyed scratch with the padded planner tables.

    Rows past ``n`` get the inert pad values every call (a previous call on
    the same bucket may have staged more live groups).  Returns
    ``(scratch, b_p, n, Gp)``; ``b_p`` is a broadcast view, never written.
    """
    n = est.num_groups
    L = est.num_arms
    Gp = bucket_size(n, group_bucket)
    T = est.responses.shape[1]
    scr = _plan_scratch(Gp, L, T)
    scr["resp"][:n] = est.responses_t
    scr["resp"][n:] = -1
    scr["valid"][:n] = est.valid
    scr["valid"][n:] = 0.0
    scr["w"][:n] = est.log_weights
    scr["w"][n:] = 0.0
    scr["empty"][:n] = est.empty
    scr["empty"][n:] = 0.0
    scr["theta"][:n] = est.theta_f
    scr["theta"][n:] = 1.0
    scr["p"][:n] = est.ps
    scr["p"][n:] = 0.5
    # the gamma survival factors, elementwise in-place (the same
    # `np.exp(np.log1p(-p))` values `_greedy_gamma` precomputes serially)
    np.negative(scr["p"], out=scr["m"])
    np.log1p(scr["m"], out=scr["m"])
    np.exp(scr["m"], out=scr["m"])
    scr["budgets"][:n] = budgets_live
    scr["budgets"][n:] = -1.0               # pad groups afford nothing
    b_p = np.broadcast_to(b, (Gp, L))
    return scr, b_p, n, Gp


def _live_split(ps, b, budgets, K):
    """Serial early-return for groups that afford nothing; the rest plan."""
    G = ps.shape[0]
    results: List[Optional[SelectionResult]] = [None] * G
    live: List[int] = []
    for g in range(G):
        if (b <= budgets[g] + 1e-15).any():
            live.append(g)
        else:
            results[g] = SelectionResult(
                chosen=np.zeros(0, np.int64), xi_est=1.0 / K, cost=0.0,
                budget=float(budgets[g]),
            )
    return results, live


def sur_greedy_many(
    ps: np.ndarray,
    b: np.ndarray,
    budgets: np.ndarray,
    num_classes: int,
    key: jax.Array,
    thetas,
    use_kernel: bool = False,
    group_bucket: int = 8,
    donate: bool = True,
) -> List[SelectionResult]:
    """SurGreedyLLM over G stacked (p-vector, budget) groups — the batched
    planner plane.

    One :class:`GroupedXiEstimator` shares the CRN draws and ONE
    :func:`_sur_greedy_scan` dispatch runs everything: every group's
    greedy-on-xi, greedy-on-gamma, the best affordable single arm, and the
    Alg. 2 candidate scoring (``final_xi``) — there is no per-group Python
    work between staging the tables and reading back the planned sets.
    Under the same ``key`` the results bit-match ``[sur_greedy(ps[g], b,
    budgets[g], ...) for g]``; groups are padded to ``group_bucket``
    multiples so serving replans reuse a handful of compiled programs, and
    the padded staging buffers are reused from bucket-keyed scratch.

    Args:
      ps: (G, L) per-group success probabilities.
      b: (L,) shared pool costs.
      budgets: (G,) per-group budgets.
      thetas: scalar or (G,) Monte-Carlo sample counts.
      donate: donate the staged response/valid/weight tables to XLA
        (bit-identical either way; ``False`` keeps the transferred device
        copies alive for callers that want to inspect them).
    """
    ps = clip_probs(np.atleast_2d(np.asarray(ps, np.float64)))
    G, L = ps.shape
    b = np.asarray(b, np.float64)
    budgets = np.broadcast_to(np.asarray(budgets, np.float64), (G,))
    thetas = np.broadcast_to(np.asarray(thetas, np.int64), (G,))
    K = int(num_classes)

    results, live = _live_split(ps, b, budgets, K)
    if not live:
        return results

    est = GroupedXiEstimator(
        key, ps[live], K, thetas[live], use_kernel=use_kernel
    )
    scr, b_p, n, _ = _stage_groups(est, b, budgets[live], group_bucket)
    scan_fn = _sur_greedy_scan if donate else _sur_greedy_scan_nodonate
    with x64(), _quiet_donation():
        out = scan_fn(
            scr["resp"], scr["valid"], scr["w"], scr["empty"], scr["theta"],
            scr["p"], b_p, scr["budgets"], scr["m"],
            num_classes=K, full=True,
        )
    picks, npick, g_picks, g_npick, l_star, xi_vals = (
        np.asarray(o) for o in out
    )

    for i, g in enumerate(live):
        s1 = [int(a) for a in picks[i, : npick[i]]]
        s2 = [int(a) for a in g_picks[i, : g_npick[i]]]
        results[g] = _assemble_result(
            est.ps[i], b, float(budgets[g]), int(l_star[i]), s1, s2,
            xi_vals[i],
        )
    return results


def _sur_greedy_many_hostgamma(
    ps: np.ndarray,
    b: np.ndarray,
    budgets: np.ndarray,
    num_classes: int,
    key: jax.Array,
    thetas,
    use_kernel: bool = False,
    group_bucket: int = 8,
) -> List[SelectionResult]:
    """The PR 9 planner plane, kept verbatim as reference and bench
    baseline: the device scan runs greedy-on-xi only (``full=False``, no
    donation), then a per-group host loop runs greedy-on-gamma / l* and
    ``est.final_xi`` stages the candidate tables back through a separate
    dispatch. Bit-identical to :func:`sur_greedy_many`; strictly more
    host work per group."""
    ps = clip_probs(np.atleast_2d(np.asarray(ps, np.float64)))
    G, L = ps.shape
    b = np.asarray(b, np.float64)
    budgets = np.broadcast_to(np.asarray(budgets, np.float64), (G,))
    thetas = np.broadcast_to(np.asarray(thetas, np.int64), (G,))
    K = int(num_classes)

    results, live = _live_split(ps, b, budgets, K)
    if not live:
        return results

    est = GroupedXiEstimator(
        key, ps[live], K, thetas[live], use_kernel=use_kernel
    )
    n = len(live)
    Gp = bucket_size(n, group_bucket)
    T = est.responses.shape[1]
    resp_p = np.full((Gp, L, T), -1, np.int32)
    resp_p[:n] = est.responses_t
    valid_p = np.zeros((Gp, T), np.float32)
    valid_p[:n] = est.valid
    w_p = np.zeros((Gp, L), np.float32)
    w_p[:n] = est.log_weights
    empty_p = np.zeros(Gp, np.float32)
    empty_p[:n] = est.empty
    theta_p = np.ones(Gp, np.float64)
    theta_p[:n] = est.theta_f
    p_p = np.full((Gp, L), 0.5, np.float64)
    p_p[:n] = est.ps
    b_p = np.broadcast_to(b, (Gp, L))
    budgets_p = np.full(Gp, -1.0, np.float64)
    budgets_p[:n] = budgets[live]
    m_p = np.exp(np.log1p(-p_p))

    with x64():
        picks, npick, _, _, s1_raw, s1_cnt = _sur_greedy_scan_nodonate(
            resp_p, valid_p, w_p, empty_p, theta_p, p_p, b_p, budgets_p,
            m_p, num_classes=K, full=False,
        )
    picks = np.asarray(picks)
    npick = np.asarray(npick)
    s1_raw = np.asarray(s1_raw)[:n]
    s1_cnt = np.asarray(s1_cnt)[:n]

    l_stars: List[int] = []
    s1s: List[List[int]] = []
    s2s: List[List[int]] = []
    for i, g in enumerate(live):
        p_g = est.ps[i]
        afford = np.flatnonzero(b <= budgets[g] + 1e-15)
        l_stars.append(int(afford[np.argmax(p_g[afford])]))
        s1s.append([int(a) for a in picks[i, : npick[i]]])
        s2s.append(_greedy_gamma(p_g, b, budgets[g])[0])

    xi_vals = est.final_xi(l_stars, s1s, s2s, s1_raw, s1_cnt)  # (n, 3) f64
    for i, g in enumerate(live):
        results[g] = _assemble_result(
            est.ps[i], b, float(budgets[g]), l_stars[i], s1s[i], s2s[i],
            xi_vals[i],
        )
    return results


def adaptive_invoke(
    selection: Sequence[int],
    p: np.ndarray,
    num_classes: int,
    invoke_fn: Callable[[int], int],
    p_all: Optional[np.ndarray] = None,
    rng: Optional[np.random.Generator] = None,
    costs: Optional[np.ndarray] = None,
) -> InvocationResult:
    """Adaptive invocation (Algorithm 3 lines 3-11).

    Invokes arms of ``selection`` in decreasing-p order and early-stops when
    the residual potential belief F(T*) can no longer change the prediction:
    ``F(T*) * H2(phi) <= H1(phi)`` (Prop. 4 guarantees prediction equality
    with the full set).

    Args:
      invoke_fn: ``arm_index -> class_id`` — runs the real model (or oracle).
    """
    p = clip_probs(p)
    K = int(num_classes)
    w = log_weight(p, K)
    empty = empty_log_belief(p if p_all is None else p_all)
    sel = sorted(selection, key=lambda i: -p[i])
    remaining = list(sel)

    used: List[int] = []
    responses: List[int] = []
    beliefs = np.full(K, empty, np.float64)
    counts = np.zeros(K, np.int64)

    while remaining:
        log_f = float(np.sum(w[remaining]))
        h1, h2, _ = top2_beliefs(beliefs)
        if not (log_f + h2 > h1 - STOP_MARGIN):
            break  # residual arms cannot flip the prediction (Prop. 4)
        arm = remaining.pop(0)
        r = int(invoke_fn(arm))
        used.append(arm)
        responses.append(r)
        if counts[r] == 0:
            beliefs[r] = w[arm]
        else:
            beliefs[r] += w[arm]
        counts[r] += 1

    pred, _ = predict_from_beliefs(beliefs, rng)
    cost_vec = np.asarray(costs, np.float64) if costs is not None else np.zeros(p.size)
    return InvocationResult(
        prediction=int(pred),
        used=np.asarray(used, np.int64),
        responses=np.asarray(responses, np.int64),
        cost=float(cost_vec[used].sum()) if used else 0.0,
        planned_cost=float(cost_vec[list(sel)].sum()) if len(sel) else 0.0,
        log_beliefs=beliefs,
    )


@dataclasses.dataclass
class ThriftLLM:
    """End-to-end selector (Algorithm 3): SurGreedy selection + adaptive
    invocation, parameterized by the paper's (eps, delta).

    One instance is bound to a pool (costs) and reused across query classes;
    per-class selections are cached because selection depends only on
    (p-vector, K, budget).
    """

    costs: np.ndarray
    eps: float = 0.1
    delta: float = 0.01
    seed: int = 0
    use_kernel: bool = False

    def __post_init__(self):
        self.costs = np.asarray(self.costs, np.float64)
        self._cache: dict = {}

    def rebind_costs(self, costs: np.ndarray) -> None:
        """Swap in a new pool cost vector and drop every cached selection.

        Selections depend on prices, so they cannot survive a re-pricing;
        the serving PlanService calls this when the pool fingerprint
        changes (see :meth:`repro.serving.plans.PlanService.refresh`).
        """
        self.costs = np.asarray(costs, np.float64)
        self._cache.clear()

    def trim_cache(self, max_entries: int) -> int:
        """Drop the oldest cached selections beyond ``max_entries``.

        Selection keys embed the p-vector, so once an estimate moves (the
        online-feedback steady state) its old entries can never be hit
        again — without trimming, continuous drift would grow the memo
        indefinitely. Insertion order doubles as age (never-rekeyed dict).
        Returns the number of entries dropped."""
        drop = len(self._cache) - int(max_entries)
        if drop <= 0:
            return 0
        for key in list(self._cache)[:drop]:
            del self._cache[key]
        return drop

    def theta(self, p: np.ndarray, budget: float) -> int:
        afford = np.flatnonzero(self.costs <= budget + 1e-15)
        p_star = float(np.max(clip_probs(p)[afford])) if afford.size else 1.0
        return theta_for(self.eps, self.delta, p_star, len(self.costs))

    @staticmethod
    def _memo_key(p: np.ndarray, num_classes: int, budget: float):
        return (
            np.round(np.asarray(p, np.float64), 12).tobytes(), num_classes,
            budget,
        )

    def select(self, p: np.ndarray, num_classes: int, budget: float) -> SelectionResult:
        key_tuple = self._memo_key(p, num_classes, budget)
        if key_tuple in self._cache:
            return self._cache[key_tuple]
        res = sur_greedy(
            p,
            self.costs,
            budget,
            num_classes,
            jax.random.key(self.seed),
            self.theta(p, budget),
            use_kernel=self.use_kernel,
        )
        self._cache[key_tuple] = res
        return res

    def select_many(
        self,
        ps: np.ndarray,
        num_classes: int,
        budgets,
        max_group: int = 64,
    ) -> List[SelectionResult]:
        """Batched :meth:`select` over stacked (p-vector, budget) pairs.

        Cache-consistent with the serial path: cached pairs are returned
        as-is, the misses are planned by :func:`sur_greedy_many` in one
        device program (chunked at ``max_group`` groups to bound peak
        memory) and memoized under the same keys — so serial and batched
        callers share one selection cache and, by the planner's CRN
        contract, identical results.
        """
        ps = np.atleast_2d(np.asarray(ps, np.float64))
        G = ps.shape[0]
        budgets = np.broadcast_to(np.asarray(budgets, np.float64), (G,))
        keys = [
            self._memo_key(ps[g], num_classes, float(budgets[g]))
            for g in range(G)
        ]
        miss: List[int] = []
        seen = set()
        for g, k in enumerate(keys):
            if k not in self._cache and k not in seen:
                miss.append(g)
                seen.add(k)
        for s in range(0, len(miss), max_group):
            chunk = miss[s:s + max_group]
            thetas = np.asarray(
                [self.theta(ps[g], float(budgets[g])) for g in chunk], np.int64
            )
            res = sur_greedy_many(
                ps[chunk],
                self.costs,
                budgets[chunk],
                num_classes,
                jax.random.key(self.seed),
                thetas,
                use_kernel=self.use_kernel,
            )
            for g, r in zip(chunk, res):
                self._cache[keys[g]] = r
        return [self._cache[k] for k in keys]

    def answer(
        self,
        p: np.ndarray,
        num_classes: int,
        budget: float,
        invoke_fn: Callable[[int], int],
        rng: Optional[np.random.Generator] = None,
    ) -> InvocationResult:
        sel = self.select(p, num_classes, budget)
        return adaptive_invoke(
            list(sel.chosen), p, num_classes, invoke_fn, rng=rng, costs=self.costs
        )
