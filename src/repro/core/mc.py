"""Monte-Carlo estimation of the correctness probability (Lemma 4).

The estimator draws ``theta`` synthetic observations of the *whole pool* once
(common random numbers) and evaluates any candidate subset as a masked belief
contraction over those shared draws. CRN pairs the greedy comparisons, which
substantially reduces the variance of marginal-gain rankings and means one
``sample + one-hot`` materialization serves an entire SurGreedyLLM run.

The masked evaluation is a dense ``(C, L) x (theta, L, K)`` contraction — the
TPU hot-spot of the selector. ``repro.kernels.mc_correctness`` implements it
as a Pallas kernel with theta-tiling; :func:`xi_from_responses` is its oracle.

Batched planning (`sur_greedy_many`) stacks G groups' draws into one
:class:`GroupedXiEstimator` over ``(G, theta_max, L)`` response tensors, so
a whole (cluster, budget) batch shares one device program per greedy round.
The grouped evaluators (:func:`xi_from_responses_grouped`,
:func:`xi_marginal_grouped`) are written for *bit-stability*: every
floating-point reduction is either exact (integer-valued tie counts,
order-independent in any tiling/padding/batching) or an elementwise chain in
a fixed order, so group g's xi values are bitwise identical whether it is
evaluated alone (G=1, theta_g draws) or inside a padded (G, theta_max)
batch. That is the contract the batched-vs-serial equivalence suite pins.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from .x64 import x64

from .belief import empty_log_belief, log_weight
from .types import clip_probs

TIE_TOL = 1e-6


def theta_for(eps: float, delta: float, p_star: float, num_arms: int) -> int:
    """theta = (8 + 2 eps) / (eps^2 p*) * ln(2 L^2 / delta)  (Algorithm 3)."""
    p_star = max(p_star, 1e-6)
    theta = (8.0 + 2.0 * eps) / (eps * eps * p_star) * math.log(2.0 * num_arms * num_arms / delta)
    return int(math.ceil(theta))


def _draw_rows(key: jax.Array, num_arms: int, num_classes: int, theta: int):
    """(theta, L) uniform + wrong-class draws whose row ``t`` depends only
    on ``(key, t)`` (per-row ``fold_in``), never on ``theta``.

    This counter-stability is what lets the grouped sampler draw ONE
    ``(theta_max, L)`` tensor and hand every group its own prefix — bitwise
    the draws :func:`sample_pool_responses` would make for that group's
    theta alone.
    """
    ku, kc = jax.random.split(key)

    def row(t):
        u = jax.random.uniform(jax.random.fold_in(ku, t), (num_arms,))
        wrong = jax.random.randint(
            jax.random.fold_in(kc, t), (num_arms,), 1, num_classes
        )
        return u, wrong

    return jax.vmap(row)(jnp.arange(theta, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_classes", "theta"))
def sample_pool_responses(
    key: jax.Array, p: jnp.ndarray, num_classes: int, theta: int
) -> jnp.ndarray:
    """(theta, L) int32 responses of every arm, ground truth = class 0.

    Arm i answers 0 w.p. p_i, else uniformly one of the K-1 wrong classes.
    """
    u, wrong = _draw_rows(key, p.shape[0], num_classes, theta)
    return jnp.where(u < p[None, :], 0, wrong).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_classes", "theta"))
def sample_pool_responses_grouped(
    key: jax.Array, ps: jnp.ndarray, num_classes: int, theta: int
) -> jnp.ndarray:
    """(G, theta, L) responses for G groups sharing one CRN draw tensor.

    Group g's rows ``[:theta_g]`` are bitwise identical to
    ``sample_pool_responses(key, ps[g], num_classes, theta_g)`` — the
    per-row ``fold_in`` makes draws prefix-stable, so one dispatch serves
    every ragged theta (callers mask rows past each group's own theta).
    """
    u, wrong = _draw_rows(key, ps.shape[1], num_classes, theta)
    return jnp.where(u[None] < ps[:, None, :], 0, wrong[None]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_classes",))
def xi_from_responses(
    responses: jnp.ndarray,     # (theta, L) int32
    masks: jnp.ndarray,         # (C, L) float32 subset indicators
    log_weights: jnp.ndarray,   # (L,) float32
    empty_belief: jnp.ndarray,  # scalar float32
    num_classes: int,
) -> jnp.ndarray:
    """Estimate xi for C candidate subsets from shared response draws.

    Returns (C,) float32. Fractional tie credit reproduces random
    tie-breaking in expectation. This function is the pure-jnp oracle of the
    ``mc_correctness`` Pallas kernel.
    """
    onehot = jax.nn.one_hot(responses, num_classes, dtype=jnp.float32)  # (T, L, K)
    mw = masks * log_weights[None, :]                                   # (C, L)
    beliefs = jnp.einsum("cl,tlk->ctk", mw, onehot)  # thriftlint: ignore[f64-reduction] (C,T,K) f32 by design: this is the bit-level oracle of the f32 mc_correctness kernel
    counts = jnp.einsum("cl,tlk->ctk", masks, onehot)  # thriftlint: ignore[f64-reduction] f32 by design: kernel-oracle parity (and counts are exact small ints)
    beliefs = jnp.where(counts > 0, beliefs, empty_belief)
    mx = jnp.max(beliefs, axis=-1, keepdims=True)
    is_max = (beliefs >= mx - TIE_TOL).astype(jnp.float32)
    ties = jnp.sum(is_max, axis=-1)  # thriftlint: ignore[f64-reduction] exact: sums K indicator values, K << 2^24
    credit = is_max[:, :, 0] / ties
    return jnp.mean(credit, axis=-1)  # thriftlint: ignore[f64-reduction] f32 by design: the kernel reduces credit in f32; serial oracle must match it bitwise


class McXiEstimator:
    """Stateful CRN estimator bound to one (pool, query-class) pair.

    Usage::

        est = McXiEstimator(key, p, K, theta)
        vals = est(masks)          # (C,) numpy
        x    = est.xi(indices)     # scalar
    """

    def __init__(
        self,
        key: jax.Array,
        p: np.ndarray,
        num_classes: int,
        theta: int,
        p_all: Optional[np.ndarray] = None,
        use_kernel: bool = False,
    ):
        self.p = clip_probs(p)
        self.num_arms = int(self.p.size)
        self.num_classes = int(num_classes)
        self.theta = int(theta)
        self.use_kernel = use_kernel
        self._w = jnp.asarray(log_weight(self.p, self.num_classes), jnp.float32)
        self._empty = jnp.float32(
            empty_log_belief(self.p if p_all is None else p_all)
        )
        self._responses = sample_pool_responses(
            key, jnp.asarray(self.p, jnp.float32), self.num_classes, self.theta
        )

    def __call__(self, masks: np.ndarray) -> np.ndarray:
        masks = jnp.asarray(np.atleast_2d(masks), jnp.float32)
        if self.use_kernel:
            from repro.kernels import ops as kernel_ops  # lazy: optional dep

            vals = kernel_ops.mc_correctness(
                self._responses, masks, self._w, self._empty, self.num_classes
            )
        else:
            vals = xi_from_responses(
                self._responses, masks, self._w, self._empty, self.num_classes
            )
        return np.asarray(vals)

    def xi(self, indices) -> float:
        mask = np.zeros(self.num_arms, np.float32)
        if len(indices):
            mask[np.asarray(indices, np.int64)] = 1.0
        return float(self(mask[None, :])[0])


# ---------------------------------------------------------------------------
# Grouped (batched-planner) evaluation
# ---------------------------------------------------------------------------


def bucket_size(n: int, base: int) -> int:
    """Round ``n`` up to a compile bucket: multiples of ``base`` up to
    ``4 * base``, powers of two beyond (same policy as the serving router's
    wave buckets) — the grouped programs compile once per bucket instead of
    once per exact (G, theta)."""
    n = max(1, int(n))
    if n <= 4 * base:
        return max(base, -(-n // base) * base)
    m = 4 * base
    while m < n:
        m *= 2
    return m


def _hist_from_ties(hit0: jnp.ndarray, ties: jnp.ndarray, num_classes: int):
    """(hit0 (..., T) bool, ties (..., T) i32) -> (..., K) f32 counts.

    ``counts[..., j]`` = number of draws where class 0 attains the maximum
    belief with exactly ``j + 1`` classes tied. Every reduction sums
    integer-valued f32 (exact below 2^24), so the result is independent of
    summation order, padding and batching — the bit-stability anchor of
    the batched planner.
    """
    one = jnp.float32(1.0)
    zero = jnp.float32(0.0)
    return jnp.stack(
        [
            jnp.sum(jnp.where(hit0 & (ties == j + 1), one, zero), axis=-1)  # thriftlint: ignore[f64-reduction] exact: 0/1 indicator counts below 2^24, order-free by construction (see docstring)
            for j in range(num_classes)
        ],
        axis=-1,
    )


def _xi_from_ties(hit0: jnp.ndarray, ties: jnp.ndarray, theta: jnp.ndarray,
                  num_classes: int):
    """Exact fractional-credit mean from per-draw (hit0, ties) configs.

    Fast path (lcm(1..K) < 2^24, i.e. K <= 17): each draw's credit
    ``1/ties`` is scaled by the lcm into an exact small integer, summed
    exactly (f64 accumulator, order-free), and divided once — a single
    reduction instead of a per-tie-count histogram. Beyond that the
    histogram path keeps exactness. Both are bitwise batching-invariant
    and the planes share whichever branch K selects.
    """
    lcm = math.lcm(*range(1, num_classes + 1))
    if lcm < (1 << 24):
        # f32 division is exact here: ties divides the lcm, so the true
        # quotient is an integer < 2^24 and correct rounding returns it
        scaled = jnp.float32(lcm) / jnp.maximum(ties, 1).astype(jnp.float32)
        credit = jnp.where(hit0, scaled, jnp.float32(0.0))
        s = jnp.sum(credit, axis=-1, dtype=jnp.float64)
        return s / (theta * np.float64(lcm))
    hist = _hist_from_ties(hit0, ties, num_classes)
    return _xi_from_hist(hist, theta, num_classes)


def _tie_histogram(disp: jnp.ndarray, valid: jnp.ndarray, num_classes: int):
    """Per-draw tie bookkeeping of the fractional-credit estimator.

    ``disp`` is ``(..., T, K)`` displayed log-beliefs; ``valid`` broadcasts
    over the draw axis with 0 marking padding. Returns the per-draw
    ``(hit0, ties)`` max/tie configuration.
    """
    mx = jnp.max(disp, axis=-1, keepdims=True)
    is_max = disp >= mx - TIE_TOL
    ties = jnp.sum(is_max.astype(jnp.int32), axis=-1)     # (..., T)
    hit0 = is_max[..., 0] & (valid > 0)
    return hit0, ties


def _xi_from_hist(hist: jnp.ndarray, theta: jnp.ndarray, num_classes: int):
    """Exact tie-count histogram -> xi, in float64.

    ``xi = (sum_j hist_j / (j + 1)) / theta`` evaluated as a fixed-order
    elementwise chain — deterministic IEEE ops, so the value per group does
    not depend on how many groups share the program.
    """
    acc = hist[..., 0].astype(jnp.float64)
    for j in range(1, num_classes):
        acc = acc + hist[..., j].astype(jnp.float64) / np.float64(j + 1)
    return acc / theta


def _masked_xi_core(responses, masks, log_weights, empty, valid, theta,
                    num_classes: int):
    """xi of C arbitrary (binary-mask) subsets per group.

    responses: (G, T, L) int32, -1 past each group's theta.
    masks:     (G, C, L) f32 0/1 subset indicators.
    log_weights: (G, L) f32; empty: (G,) f32; valid: (G, T) f32;
    theta: (G,) f64. Returns (G, C) f64.

    Belief accumulation is an explicit chain over the (static) arm axis in
    ascending index order — no dot contraction whose reduction tree could
    vary with shape — so per-group values are batching-invariant.
    """
    G, T, L = responses.shape
    K = num_classes
    C = masks.shape[1]
    oh = responses[..., None] == jnp.arange(K, dtype=responses.dtype)
    raw = jnp.zeros((G, C, T, K), jnp.float32)
    cnt = jnp.zeros((G, C, T, K), jnp.int32)
    for l in range(L):
        sel = (masks[:, :, l] > 0)[:, :, None, None] & oh[:, :, l, :][:, None]
        w_l = log_weights[:, l][:, None, None, None]
        raw = jnp.where(sel, raw + w_l, raw)
        cnt = cnt + sel.astype(jnp.int32)
    disp = jnp.where(cnt > 0, raw, empty[:, None, None, None])
    hit0, ties = _tie_histogram(disp, valid[:, None, :], K)
    return _xi_from_ties(hit0, ties, theta[:, None], K)


def _marginal_xi_core(resp_t, base_raw, base_cnt, log_weights, empty,
                      valid, theta, num_classes: int):
    """xi of (current set ∪ {l}) for every arm l, per group.

    The greedy hot path: the current set's belief table ``(base_raw,
    base_cnt)`` (accumulated incrementally in pick order) is extended by one
    arm's response column, so a round costs O(G L theta K) elementwise work
    instead of rebuilding every candidate mask from scratch. A candidate's
    response touches exactly one class per draw, so the displayed beliefs
    are one ``where`` over the (precomputed, L-independent) base display
    table — the same IEEE values the mask chain produces, with half the
    memory traffic.

    A candidate only moves ONE class's belief per draw, so instead of
    materializing the (G, L, T, K) modified tables this decomposes against
    the base's exact top-2: with ``a`` the modified class's new value and
    ``excl`` the exact max over the other classes, the new max is
    ``max(a, excl)`` and the tie count is recovered from per-class
    threshold counts. All selections (max, second max, duplicate count)
    are exact, so the result is bitwise the naive per-candidate max — at a
    fraction of the memory traffic.

    resp_t: (G, L, T) int32 wave-major-transposed responses;
    base_raw: (G, T, K) f32; base_cnt: (G, T, K) int32. Returns (G, L) f64.
    """
    K = num_classes
    G, L, T = resp_t.shape
    base_disp = jnp.where(base_cnt > 0, base_raw, empty[:, None, None])

    # exact top-2 of the base display, plus the max's multiplicity
    m1 = jnp.full((G, T), -jnp.inf, base_disp.dtype)
    m2 = m1
    c1 = jnp.zeros((G, T), jnp.int32)
    for k in range(K):
        v = base_disp[:, :, k]
        gt = v > m1
        eq = v == m1
        m2 = jnp.where(gt, m1, jnp.maximum(m2, v))
        c1 = jnp.where(gt, 1, jnp.where(eq, c1 + 1, c1))
        m1 = jnp.where(gt, v, m1)

    is_mod = resp_t >= 0                                  # -1 = no response
    kc = jnp.maximum(resp_t, 0)                           # (G, L, T)
    # per-draw class select as a K-step where chain (CPU-vectorizable,
    # unlike a general gather); selects exact values, order-free
    rawstar = jnp.broadcast_to(base_raw[:, None, :, 0], (G, L, T))
    dispstar = jnp.broadcast_to(base_disp[:, None, :, 0], (G, L, T))
    for k in range(1, K):
        hit = kc == k
        rawstar = jnp.where(hit, base_raw[:, None, :, k], rawstar)
        dispstar = jnp.where(hit, base_disp[:, None, :, k], dispstar)
    # the modified class's new value; an unmodified draw keeps its display
    a = jnp.where(is_mod, rawstar + log_weights[:, :, None], dispstar)
    excl = jnp.where(
        dispstar == m1[:, None, :],
        jnp.where(c1[:, None, :] >= 2, m1[:, None, :], m2[:, None, :]),
        m1[:, None, :],
    )                                                     # exact max over k != k*
    mx = jnp.maximum(a, excl)
    thr = mx - TIE_TOL
    # count of classes >= thr in the modified display: the candidate's own
    # class compares at `a`, every other class at its base display
    n_ge = jnp.zeros((G, L, T), jnp.int32)
    for k in range(K):
        n_ge = n_ge + (base_disp[:, :, k][:, None, :] >= thr).astype(jnp.int32)
    ties = (a >= thr).astype(jnp.int32) + n_ge - (dispstar >= thr).astype(jnp.int32)
    disp0 = jnp.where(
        is_mod & (kc == 0), a, base_disp[:, :, 0][:, None, :]
    )
    hit0 = (disp0 >= thr) & (valid[:, None, :] > 0)
    return _xi_from_ties(hit0, ties, theta[:, None], K)


def _tables_xi_core(base_raw, base_cnt, empty, valid, theta, num_classes: int):
    """xi from prebuilt belief tables — the cheap final-candidate path.

    ``base_raw``/``base_cnt`` are (G, C, T, K) tables accumulated on the
    host in ascending arm order (the same operand sequence as the mask
    chain in :func:`_masked_xi_core`, hence the same IEEE values); the
    device only pays the empty-class display, the tie histogram and the
    combine. Returns (G, C) f64.
    """
    disp = jnp.where(base_cnt > 0, base_raw, empty[:, None, None, None])
    hit0, ties = _tie_histogram(disp, valid[:, None, :], num_classes)
    return _xi_from_ties(hit0, ties, theta[:, None], num_classes)


xi_from_responses_grouped = functools.partial(
    jax.jit, static_argnames=("num_classes",)
)(_masked_xi_core)

xi_marginal_grouped = functools.partial(
    jax.jit, static_argnames=("num_classes",)
)(_marginal_xi_core)

xi_from_tables_grouped = functools.partial(
    jax.jit, static_argnames=("num_classes",)
)(_tables_xi_core)


class GroupedXiEstimator:
    """The CRN estimator reshaped over G groups for the batched planner.

    Each group g gets exactly the draws the serial :class:`McXiEstimator`
    would sample for it — ``sample_pool_responses(key, p_g, K, theta_g)``
    with the *shared* key — stacked into one ``(G, theta_max, L)`` tensor
    (padded with -1 responses and a 0 ``valid`` mask past each group's own
    theta, ``theta_max`` rounded up to a compile bucket). Mask evaluation
    and the greedy's marginal-gain evaluation are then single dispatches
    covering every group.

    Usage::

        est = GroupedXiEstimator(key, ps, K, thetas)    # ps (G, L)
        vals = est(masks)                               # (G, C) f64
        gains = est.marginal(base_raw, base_cnt)        # (G, L) f64
    """

    def __init__(
        self,
        key: jax.Array,
        ps: np.ndarray,
        num_classes: int,
        thetas,
        p_all: Optional[np.ndarray] = None,
        use_kernel: bool = False,
        tile: int = 256,
    ):
        ps = clip_probs(np.atleast_2d(np.asarray(ps, np.float64)))
        G, L = ps.shape
        self.ps = ps
        self.num_groups = G
        self.num_arms = L
        self.num_classes = int(num_classes)
        self.use_kernel = bool(use_kernel)
        thetas = np.broadcast_to(np.asarray(thetas, np.int64), (G,))
        self.thetas = thetas
        Tp = bucket_size(int(thetas.max()), tile)
        # one dispatch samples every group's draws (prefix-stable rows);
        # rows past each group's own theta are masked to -1 / invalid
        self.responses = np.array(
            sample_pool_responses_grouped(
                key, jnp.asarray(ps, jnp.float32), self.num_classes, Tp
            )
        )
        self.valid = (
            np.arange(Tp)[None, :] < thetas[:, None]
        ).astype(np.float32)
        self.responses[self.valid == 0.0] = -1
        # candidate-major layout for the greedy's marginal evaluation
        self.responses_t = np.ascontiguousarray(
            self.responses.transpose(0, 2, 1)
        )
        # vectorized over groups: `log_weight` is elementwise and the empty
        # belief is a row-min chain, so these are the exact per-group
        # `log_weight(ps[g], K)` / `empty_log_belief(base[g])` bits
        self.log_weights = log_weight(ps, self.num_classes).astype(np.float32)
        base = ps if p_all is None else clip_probs(
            np.broadcast_to(np.atleast_2d(np.asarray(p_all, np.float64)), (G, L))
        )
        p_min = np.min(clip_probs(base), axis=1)
        self.empty = (
            np.log(p_min) - np.log(2.0) - np.log1p(-p_min)
        ).astype(np.float32)
        self.theta_f = thetas.astype(np.float64)

    def __call__(self, masks: np.ndarray) -> np.ndarray:
        """(G, C, L) binary masks -> (G, C) xi estimates (f64 numpy)."""
        masks = np.asarray(masks, np.float32)
        if self.use_kernel:
            from repro.kernels import ops as kernel_ops  # lazy: optional dep

            vals = kernel_ops.mc_correctness_grouped(
                jnp.asarray(self.responses), jnp.asarray(masks),
                jnp.asarray(self.log_weights), jnp.asarray(self.empty),
                jnp.asarray(self.valid),
                jnp.asarray(self.theta_f, jnp.float32), self.num_classes,
            )
            return np.asarray(vals, np.float64)
        # host-accumulated belief tables (ascending arm order = the mask
        # chain's operand sequence), one cheap device pass for the rest
        G, C, L = masks.shape
        T = self.responses.shape[1]
        K = self.num_classes
        raw = np.zeros((G, C, T, K), np.float32)
        cnt = np.zeros((G, C, T, K), np.int32)
        for g in range(G):
            resp = self.responses[g]
            for c in range(C):
                for l in np.flatnonzero(masks[g, c] > 0):
                    col = resp[:, l]
                    rows = np.flatnonzero(col >= 0)
                    raw[g, c, rows, col[rows]] += self.log_weights[g, l]
                    cnt[g, c, rows, col[rows]] += 1
        with x64():
            vals = xi_from_tables_grouped(
                raw, cnt, self.empty, self.valid, self.theta_f,
                num_classes=K,
            )
        return np.asarray(vals)

    def marginal(self, base_raw: np.ndarray, base_cnt: np.ndarray) -> np.ndarray:
        """(G, T, K) current-set belief tables -> (G, L) xi of set ∪ {l}."""
        with x64():
            vals = xi_marginal_grouped(
                self.responses_t, np.asarray(base_raw, np.float32),
                np.asarray(base_cnt, np.int32), self.log_weights, self.empty,
                self.valid, self.theta_f, num_classes=self.num_classes,
            )
        return np.asarray(vals)

    def _accumulate(self, raw: np.ndarray, cnt: np.ndarray, g: int,
                    arms) -> None:
        """Fold ``arms``' response columns of group ``g`` into one (T, K)
        belief table, in the given arm order (one f32 add per draw per arm —
        the same operand sequence on every plane)."""
        resp = self.responses[g]
        t = int(self.thetas[g])
        rows = np.arange(t)
        for l in arms:
            col = resp[:t, int(l)]
            raw[rows, col] += self.log_weights[g, int(l)]
            cnt[rows, col] += 1

    def final_xi(
        self,
        l_stars,
        s1s,
        s2s,
        s1_raw: Optional[np.ndarray] = None,
        s1_cnt: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """xi of the three Alg. 2 candidates per group -> (G, 3) f64.

        The greedy already accumulated each group's s1 belief table
        (``s1_raw``/``s1_cnt``, in pick order) — it is reused as-is; the
        single-arm l* and the gamma set s2 tables are folded on the host in
        ascending arm order, and one grouped device pass scores all 3G
        candidates. The kernel backend evaluates the same three sets from
        their masks instead (mask layout is what the kernel implements).
        """
        G = self.num_groups
        L = self.num_arms
        K = self.num_classes
        if self.use_kernel or s1_raw is None:
            masks = np.zeros((G, 3, L), np.float32)
            for g in range(G):
                masks[g, 0, int(l_stars[g])] = 1.0
                if len(s1s[g]):
                    masks[g, 1, np.asarray(s1s[g], np.int64)] = 1.0
                if len(s2s[g]):
                    masks[g, 2, np.asarray(s2s[g], np.int64)] = 1.0
            return self(masks)
        T = self.responses.shape[1]
        raw = np.zeros((G, 3, T, K), np.float32)
        cnt = np.zeros((G, 3, T, K), np.int32)
        raw[:, 1] = s1_raw
        cnt[:, 1] = s1_cnt
        for g in range(G):
            self._accumulate(raw[g, 0], cnt[g, 0], g, [int(l_stars[g])])
            self._accumulate(raw[g, 2], cnt[g, 2], g, sorted(int(a) for a in s2s[g]))
        with x64():
            vals = xi_from_tables_grouped(
                raw, cnt, self.empty, self.valid, self.theta_f,
                num_classes=K,
            )
        return np.asarray(vals)
