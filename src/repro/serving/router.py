"""ThriftLLM router: per-query-class selection + batched wavefront invocation.

Serving pipeline per batch (Figure 1 of the paper, batched for TPU):
  1. embed queries, map to historical clusters -> p-hat vector per query
  2. group queries by (cluster, budget); SurGreedyLLM selection per group is
     memoized by the :class:`~repro.serving.plans.PlanService` — selection
     depends only on (cluster, budget, pool fingerprint) — and the derived
     wave plan (arm order, log-weights, Prop. 4 residuals) is what the hot
     path consumes. Hot pairs can be precomputed ahead of traffic; plan
     keys carry estimator *versions*, so a cost change or a drifting
     online-feedback fold (``serving/feedback.py``) invalidates exactly
     the plans it obsoletes — lazily, with no scan on the hot path.
  3. *wavefront* adaptive invocation across the WHOLE batch. Two data-plane
     implementations with identical semantics for deterministic arms:

     * :meth:`route_batch` (default, ``jit_waves=True``) — the **jitted
       wave loop**. The per-group plans are padded to one fixed
       (B, max_waves) layout (bucketed to limit recompilation), every
       scheduled (query, wave) response is gathered up front in a single
       heterogeneous-arm engine call, and the entire wave loop — Prop. 4
       early-stop mask, belief accumulation, in-flight carry — runs as one
       jitted on-device program in float64. Because responses are
       pre-gathered, the sequential recurrence collapses into a parallel
       prefix scan (see :func:`_wave_scan`); Python never touches the
       loop and there is one dispatch per batch.
     * :meth:`route_batch_reference` — the compacting host-side wavefront
       (PR 1). Stopped queries are dropped from the index set each wave and
       each wave issues one engine call for the rows still in flight, so
       arms are only ever invoked for queries that need them. This is the
       fallback for pools where speculative invocation costs real money
       (live LLM APIs), and the semantics pin for equivalence tests.

     The trade: the jitted loop invokes every *scheduled* (query, wave)
     cell — including waves the stop rule later masks out — so realized
     **reported** costs still count only invoked waves, but the engine does
     speculative work. For oracle/tabular/self-hosted pools that is pure
     throughput; for metered upstream APIs use ``jit_waves=False``.
  4. belief aggregation: float64 scatter tables by default, or the
     ``belief_aggregate`` Pallas kernel (``use_kernel=True``), dispatched
     from *inside* the jitted scan — identical masking semantics, float32
     accumulation on TPU. Caveat: the kernel backend evaluates the Prop. 4
     stop rule on float32 beliefs, so a query whose margin lands within
     float32 resolution (~1e-7) of the STOP_MARGIN boundary may take one
     wave more or fewer than the float64 path; everywhere else the two
     backends are identical.
"""
from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from repro.core.x64 import x64

from repro.core.belief import tie_break_argmax
from repro.core.estimation import SuccessProbEstimator
from repro.core.selection import STOP_MARGIN, ThriftLLM, adaptive_invoke
from repro.distributed.fault import (
    FAULT_DEGRADE,
    FAULT_ERROR,
    FAULT_TIMEOUT,
    failover_gather,
    observed_faults,
)
from repro.kernels import ops

from .engine import PoolEngine
from .plans import GroupPlan, PlanService, stack_plans
from .telemetry import span

# retained name for PR 1 call sites / pickles
_GroupPlan = GroupPlan


class RouteResult:
    """Batched routing output.

    One instance summarizes a whole ``route_batch`` call. Fields:

    Attributes:
      predictions: (B,) aggregated class id per query (Eq. 4 argmax with
        shared tie-breaking).
      costs: (B,) realized USD per query — only waves actually invoked.
      planned_costs: (B,) USD of each query's full selected set (the spend
        ceiling if no early stop fires; ``costs <= planned_costs`` always).
      clusters: (B,) historical cluster each query mapped to.
      budgets: (B,) per-query budget applied.
      schedule: (B, T) arm id scheduled at wave t, ``-1`` = no arm (plan
        shorter than T).
      responses: (B, T) class id returned at wave t, ``-1`` = wave not run.
      invoked: (B, T) bool — wave t really ran for this query (the Prop. 4
        stop rule had not fired and an arm was scheduled).
      arm_query_counts: (L,) number of queries each pool arm actually
        served — the scheduler's latency accounting input.
      waves: number of waves the batch executed before every query stopped.

    When the engine carries an active fault policy, ``schedule`` /
    ``responses`` / ``invoked`` / ``costs`` describe the *effective* route
    (what was actually served after in-wave failover re-routed failed
    slots), so downstream feedback/latency/ledger accounting needs no fault
    awareness, and three keyword-only fields carry the failure evidence
    (all ``None`` on fault-free routes — the common case allocates nothing):

      fault_schedule: (B, T) the original plan-order schedule.
      fault_codes: (B, T) int8 observed fault per original plan cell
        (``FAULT_TIMEOUT``/``FAULT_ERROR`` at failures the wavefront
        actually attempted, ``FAULT_DEGRADE`` at silently-degraded cells it
        actually served, 0 everywhere else — injected faults past the stop
        wave were never observed and do not count as evidence).
      arm_fault_counts: (L,) attempted timeout/error failures per arm.

    ``arms_used`` is derived lazily from the (schedule, invoked) matrices so
    the hot path never builds Python lists.
    """

    def __init__(
        self,
        predictions: np.ndarray,         # (B,)
        costs: np.ndarray,               # (B,) realized USD
        planned_costs: np.ndarray,       # (B,) full-ensemble USD
        clusters: np.ndarray,            # (B,)
        budgets: np.ndarray,             # (B,) per-query budget applied
        schedule: np.ndarray,            # (B, T) arm id per wave, -1 = none
        responses: np.ndarray,           # (B, T) class id per wave, -1 = not run
        invoked: np.ndarray,             # (B, T) bool, wave actually ran
        arm_query_counts: np.ndarray,    # (L,) queries served per arm
        waves: int,
        *,
        fault_schedule: Optional[np.ndarray] = None,   # (B, T) original plan
        fault_codes: Optional[np.ndarray] = None,      # (B, T) observed faults
        arm_fault_counts: Optional[np.ndarray] = None,  # (L,) failures per arm
    ):
        self.predictions = predictions
        self.costs = costs
        self.planned_costs = planned_costs
        self.clusters = clusters
        self.budgets = budgets
        self.schedule = schedule
        self.responses = responses
        self.invoked = invoked
        self.arm_query_counts = arm_query_counts
        self.waves = waves
        self.fault_schedule = fault_schedule
        self.fault_codes = fault_codes
        self.arm_fault_counts = arm_fault_counts
        self._arms_used: Optional[List[List[int]]] = None

    @property
    def arms_used(self) -> List[List[int]]:
        """Per query, arms actually invoked in invocation order."""
        if self._arms_used is None:
            self._arms_used = [
                self.schedule[b, self.invoked[b]].tolist()
                for b in range(self.schedule.shape[0])
            ]
        return self._arms_used

    @property
    def stop_waves(self) -> np.ndarray:
        """(B,) number of waves each query invoked before its Prop. 4 stop
        fired (== the wave index at which its result became final)."""
        return self.invoked.sum(axis=1)


# ---------------------------------------------------------------------------
# The on-device wave loop
# ---------------------------------------------------------------------------


def _bucket(n: int, *, base: int) -> int:
    """Round ``n`` up so the jitted loop compiles once per bucket instead
    of once per exact (B, T): multiples of ``base`` up to 4x base (tight —
    padded waves/rows cost real device work), powers of two beyond. One
    policy repo-wide: delegates to the planner's ``bucket_size``."""
    from repro.core.mc import bucket_size

    return bucket_size(n, base)


def _wave_scan_core(
    schedule: jnp.ndarray,    # (T, B) int32 arm ids, -1 = none (wave-major)
    responses: jnp.ndarray,   # (T, B) int32 precomputed responses, -1 = none
    weights: jnp.ndarray,     # (T, B) f64 log belief weight per wave
    residual: jnp.ndarray,    # (T, B) f64 Prop. 4 log F residuals
    src: jnp.ndarray,         # (T, B) i32 failover gather: original wave
                              #   index serving slot t (identity = no fault)
    valid: jnp.ndarray,       # (T, B) bool slot t has an available arm
    empty: jnp.ndarray,       # (B,)  f64 empty-class log belief
    stop_margin,
    *,
    num_classes: int,
    use_kernel: bool,
):
    """Entire wavefront loop as one fused on-device program.

    Because the per-wave responses are gathered up front, each query's
    trajectory is a pure *prefix* of its schedule: if it is still in flight
    at wave t it has invoked exactly waves 0..t-1. The sequential adaptive
    loop therefore collapses into a prefix scan: cumulative (T+1, B, K)
    belief tables (index t = "beliefs before wave t"), after which every
    wave's Prop. 4 stop decision is evaluated at once and each query's stop
    wave is the first failing prefix. The prefix accumulation and the
    K-class top-2 are unrolled over the static (T, K) axes into pure
    elementwise chains — XLA fuses them into a handful of kernels, the
    adds happen in exactly the host loop's sequential order (bit-identical
    float64 beliefs, no reassociation), and everything is wave-major so
    each step touches contiguous (B,)/(B, K) slabs. One compile per
    (T, B, K) bucket; the caller pads to buckets.

    Runs in float64 under ``repro.core.x64.x64``. Under
    ``use_kernel`` the prefix histories are instead aggregated by a single
    prefix-expanded ``belief_aggregate`` Pallas kernel call, so the stop
    rule sees exactly the float32 beliefs the kernel-backed reference loop
    sees (the documented ~1e-7 stop-boundary caveat).

    **In-wave failover** (``src``/``valid``): slot t of each query's wave
    program serves the plan's t-th *available* arm. The gather is computed
    host-side from the fault grid (see ``repro.distributed.fault``) and fed
    as plain data — not statics — so flipping injected faults between
    batches reuses the compiled program, and on fault-free traffic the
    identity gather is a bit-exact no-op (invalid cells read the same pad
    values — schedule -1, weight 0, residual -inf — the tables already hold
    there). The stop rule, belief prefixes and residuals all operate on the
    post-gather *effective* arrays, so a failed arm's slot re-routes to the
    plan's next-best affordable arm and the belief update is masked to
    responses actually obtained. The gathered residual is the original
    plan's suffix value at the source position — an upper bound on the
    post-failover remaining evidence, so Prop. 4 never stops earlier than a
    fault-free run would.

    Returns (stop_wave (B,) int — number of waves invoked per query,
    predictions (B,) int via first-max argmax, log-beliefs (B, K) at the
    stop wave).
    """
    T, B = schedule.shape
    K = num_classes
    f_dtype = weights.dtype
    class_ids = jnp.arange(K, dtype=responses.dtype)

    pad_i = jnp.asarray(-1, schedule.dtype)
    schedule = jnp.where(valid, jnp.take_along_axis(schedule, src, axis=0), pad_i)
    responses = jnp.where(valid, jnp.take_along_axis(responses, src, axis=0), pad_i)
    weights = jnp.where(valid, jnp.take_along_axis(weights, src, axis=0), 0.0)
    residual = jnp.where(
        valid, jnp.take_along_axis(residual, src, axis=0), -jnp.inf
    )

    if use_kernel:
        # Prefix-expanded kernel dispatch: row (b, t) holds query b's
        # response history masked to waves < t; one pallas_call aggregates
        # every prefix of every query.
        resp_bt = responses.T                               # (B, T)
        hist = jnp.where(
            jnp.arange(T + 1)[None, :, None] > jnp.arange(T)[None, None, :],
            resp_bt[:, None, :],
            -1,
        )                                                   # (B, T+1, T)
        w32 = weights.T.astype(jnp.float32)
        bel32, _ = ops.belief_aggregate(
            hist.reshape(B * (T + 1), T),
            jnp.broadcast_to(w32[:, None, :], (B, T + 1, T)).reshape(-1, T),
            jnp.broadcast_to(
                empty.astype(jnp.float32)[:, None], (B, T + 1)
            ).reshape(-1),
            K,
            tile=512,
        )
        # f32 values compared in f64, matching the reference kernel path
        bel = bel32.reshape(B, T + 1, K).astype(f_dtype).transpose(1, 0, 2)
    else:
        onehot = responses[:, :, None] == class_ids[None, None, :]  # (T,B,K)
        contrib = jnp.where(onehot, weights[:, :, None], 0.0)
        votes = [jnp.zeros((B, K), f_dtype)]
        cnts = [jnp.zeros((B, K), bool)]
        for t in range(T):
            votes.append(votes[-1] + contrib[t])
            cnts.append(cnts[-1] | onehot[t])
        cumvote = jnp.stack(votes)                          # (T+1, B, K)
        cumcnt = jnp.stack(cnts)
        bel = jnp.where(cumcnt, cumvote, empty[None, :, None])

    # online top-2 over the static K axis; ties keep h2 == h1
    h1 = jnp.full((T + 1, B), -jnp.inf, f_dtype)
    h2 = h1
    for k in range(K):
        v = bel[:, :, k]
        gt = v > h1
        h2 = jnp.where(gt, h1, jnp.maximum(h2, v))
        h1 = jnp.where(gt, v, h1)
    stop = ~((schedule >= 0) & (residual + h2[:T] > h1[:T] - stop_margin))
    s = jnp.where(stop.any(axis=0), jnp.argmax(stop, axis=0), T)  # first stop
    beliefs = jnp.take_along_axis(bel, s[None, :, None], axis=0)[0]
    # first-max argmax, identical to the host path's deterministic tie-break
    preds = jnp.argmax(beliefs, axis=-1)
    return s, preds, beliefs


@contextlib.contextmanager
def _quiet_donation():
    """Donation is declarative — XLA aliases the donated inputs it can use
    and warns once at compile time about the rest; the caller-side contract
    ("the staged buffers are dead after dispatch") is what the wrappers and
    the `donation-contract` lint rule enforce, so the partial-use warning
    is expected noise at the dispatch seams."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


#: Planes of the packed wave layout, in row order, each (Tp, Bp): schedule,
#: responses, weights, residual, failover source, valid. Row ``6 * Tp``
#: holds ``empty`` and row ``6 * Tp + 1`` the stop margin.
_WAVE_PLANES = 6


@functools.lru_cache(maxsize=None)
def _wave_pad(Tp: int, Bp: int) -> np.ndarray:
    """The packed buffer of a (Tp, Bp) bucket with every cell at its
    plane's pad value: schedule and responses -1, weight 0, residual -inf,
    source wave the identity, valid 0, empty 0. Read-only; copied per
    group."""
    buf = np.zeros((_WAVE_PLANES * Tp + 2, Bp), np.float64)
    buf[:2 * Tp] = -1.0
    buf[3 * Tp:4 * Tp] = -np.inf
    buf[4 * Tp:5 * Tp] = np.arange(Tp)[:, None]
    buf.setflags(write=False)
    return buf


def _pack_wave(sched, resp, weights, residual, src, valid, empty,
               stop_margin) -> np.ndarray:
    """One group's wave inputs as the one host buffer the wave program
    takes: float64, ``(6 * Tp + 2, Bp)`` for the compile bucket (Tp, Bp)
    of the (T, B) tables, pads as :func:`_wave_pad` (buckets, so traffic
    with drifting batch sizes and plan depths reuses a handful of compiled
    programs). The integer and boolean planes are small integers, which
    float64 holds exactly; the program casts them back. One buffer is one
    host-to-device placement per call; every further array argument would
    be one more."""
    T, B = sched.shape
    Tp, Bp = _bucket(T, base=4), _bucket(B, base=8)
    buf = _wave_pad(Tp, Bp).copy()
    for i, plane in enumerate((sched, resp, weights, residual, src, valid)):
        buf[i * Tp:i * Tp + T, :B] = plane
    buf[_WAVE_PLANES * Tp, :B] = empty
    buf[_WAVE_PLANES * Tp + 1] = stop_margin
    return buf


def _wave_scan_packed(packed, *, num_classes: int, use_kernel: bool):
    """:func:`_wave_scan_core` over a :func:`_pack_wave` buffer: static
    slices at the bucket's row offsets, the integer planes cast back
    exactly, the loop itself unchanged."""
    Tp = (packed.shape[0] - 2) // _WAVE_PLANES

    def plane(i):
        return packed[i * Tp:(i + 1) * Tp]

    return _wave_scan_core(
        plane(0).astype(jnp.int32),
        plane(1).astype(jnp.int32),
        plane(2),
        plane(3),
        plane(4).astype(jnp.int32),
        plane(5) != 0,
        packed[_WAVE_PLANES * Tp],
        packed[_WAVE_PLANES * Tp + 1, 0],
        num_classes=num_classes,
        use_kernel=use_kernel,
    )


# The serving default donates the packed buffer: `_pack_wave` builds it as
# a throwaway local (host numpy — the jit transfers a fresh device copy per
# call and donates that copy) and nothing re-reads it after the call.
# `_wave_scan_nodonate` is the bit-identical twin for callers that keep the
# staged device buffer alive (`ThriftRouter(donate_buffers=False)`). Each
# wrapper owns one compile per (T, B, K) bucket.
_wave_scan = functools.partial(
    jax.jit, static_argnames=("num_classes", "use_kernel"),
    donate_argnums=(0,),
)(_wave_scan_packed)

_wave_scan_nodonate = functools.partial(
    jax.jit, static_argnames=("num_classes", "use_kernel"),
)(_wave_scan_packed)


class PendingRoute:
    """One in-flight batched route, created by :meth:`ThriftRouter.begin_route`.

    Three kinds:

    * ``"jit"`` — the speculative jitted wave loop. Planning, the
      speculative response gather and the device dispatch already happened
      in ``begin_route``; the device program may still be running when this
      handle is returned (JAX dispatch is asynchronous), so a front-end can
      overlap the next group's host-side planning/gather with this one's
      device compute. ``result()`` blocks on the device values and
      finalizes.
    * ``"reference"`` — the compacting host wavefront, exposed wave by
      wave: each ``step()`` call evaluates the Prop. 4 stop rule, retires
      the queries whose stop fired (returning their rows — and, in
      deterministic mode, their final predictions, which can never change
      once a query stops voting), then invokes one wave of arms for the
      queries still in flight. ``result()`` steps to exhaustion and
      finalizes; outputs are bit-identical to the PR 1 loop.
    * ``"empty"`` — a zero-query batch; ``result()`` is immediate.

    The handle is single-use: ``result()`` caches and re-returns.
    """

    def __init__(self, router: "ThriftRouter", kind: str, result=None, **state):
        self.router = router
        self.kind = kind
        self.spec_cost = state.pop("spec_cost", 0.0)
        # estimator plan-version the group's plans were gathered at —
        # observability for the online-feedback loop (a served group can be
        # attributed to the estimate generation that planned it)
        self.plan_version = state.pop("plan_version", 0)
        self._result: Optional[RouteResult] = result
        if result is not None:
            return
        self.budgets = state.pop("budgets")
        self.cluster_ids = state.pop("cluster_ids")
        self.sched_T = state.pop("sched_T")
        self.w_T = state.pop("w_T")
        self.res_T = state.pop("res_T")
        self.wc_T = state.pop("wc_T")
        self.empty = state.pop("empty")
        self.planned = state.pop("planned")
        self.payloads = state.pop("payloads")
        self.stop_margin = state.pop("stop_margin")
        self.rng = state.pop("rng")
        # batch-row offset of this group inside a logically fused batch —
        # keeps per-worker fault draws identical to the fused dispatch's
        self.fault_row_offset = int(state.pop("fault_row_offset", 0))
        assert not state, f"unknown PendingRoute state {sorted(state)}"
        self.B = int(self.budgets.shape[0])
        self.T = int(self.sched_T.shape[0])
        self.L = len(router.engine.arms)
        if kind == "reference":
            self._prepare_reference_faults()
            self._init_reference()

    # ------------------------------------------------------------------
    # jit kind: speculative gather + async device dispatch
    # ------------------------------------------------------------------
    def _dispatch_jit(self):
        with span("thrift.route.gather"):
            self._gather_jit()
        with span("thrift.route.launch"):
            self._launch_jit()

    def _gather_jit(self):
        router, T, B = self.router, self.T, self.B
        sched_T, payloads = self.sched_T, self.payloads
        engine = router.engine
        codes, failed = engine.fault_grid(
            sched_T, row_offset=self.fault_row_offset
        )
        self._orig_sched_T = sched_T
        self._codes, self._failed = codes, failed
        # Speculative response gather: one heterogeneous-arm engine call for
        # every scheduled (query, wave) cell. The device program then
        # decides which cells the adaptive loop actually uses.
        if engine.pooled:
            # all-cells fast path: responses for unscheduled (-1) cells are
            # drawn on arm 0 and never read — the stop rule fires on the
            # schedule itself before any such prefix is gathered — which
            # avoids the nonzero/compact/scatter round-trip entirely.
            resp_T = engine.invoke_grid(sched_T, payloads)
        else:
            mask = sched_T >= 0
            if failed is not None:
                mask &= ~failed          # a failed arm yields no response
            _, rows_b = np.nonzero(mask)
            resp_T = np.full((T, B), -1, np.int64)
            if rows_b.size:
                resp_T[mask] = engine.invoke_rows(sched_T[mask], payloads, rows_b)
        if codes is not None:
            resp_T = np.where(failed, -1, resp_T)
            degr = codes == FAULT_DEGRADE
            if degr.any():
                # silent degradation: the arm answers (and bills), but with a
                # hash-drawn class — response-independent, so the reference
                # plane corrupts the same cells to the same classes
                resp_T = np.where(
                    degr,
                    engine.fault_policy.corrupt_grid(
                        sched_T, row_offset=self.fault_row_offset
                    ),
                    resp_T,
                )
        self.resp_T = resp_T

        # In-wave failover gather: identity on fault-free traffic. Data
        # inputs, never statics — flipping injected faults between batches
        # rides the same compiled wave program (CompileSentinel-pinned).
        if failed is not None and router.failover:
            src, valid, self._rank, self._navail = failover_gather(
                sched_T, failed
            )
        else:
            src = np.broadcast_to(np.arange(T, dtype=np.int32)[:, None], (T, B))
            valid = sched_T >= 0
            self._rank = self._navail = None
        self._src, self._valid = src, valid

    def _launch_jit(self):
        self._dev = self.router._run_wave(_pack_wave(
            self.sched_T, self.resp_T, self.w_T, self.res_T,
            self._src, self._valid, self.empty, self.stop_margin,
        ))

    def ready(self) -> bool:
        """Non-blocking: has the dispatched device program finished? Host-
        driven kinds (reference/empty) are always ready."""
        if self.kind != "jit" or self._result is not None:
            return True
        probe = getattr(self._dev[0], "is_ready", None)
        return bool(probe()) if probe is not None else True

    def _fault_kwargs(self, stop_wave: np.ndarray) -> dict:
        """Fault-evidence fields for RouteResult; {} on fault-free routes."""
        codes = getattr(self, "_codes", None)
        if codes is None:
            return {}
        obs = observed_faults(
            codes, self._orig_sched_T, stop_wave, self._rank, self._navail
        )
        hit = (obs == FAULT_TIMEOUT) | (obs == FAULT_ERROR)
        return dict(
            fault_schedule=self._orig_sched_T.T,
            fault_codes=obs.T,
            arm_fault_counts=np.bincount(
                self._orig_sched_T[hit], minlength=self.L
            ),
        )

    def _finalize_jit(self) -> RouteResult:
        with span("thrift.finalize.wait"):
            # the outputs of one execution finish together; waiting on one
            # is cheaper than jax.block_until_ready on all three
            self._dev[0].block_until_ready()
        with span("thrift.finalize"):
            return self._finalize_jit_host()

    def _finalize_jit_host(self) -> RouteResult:
        s_d, pred_d, beliefs_d = self._dev
        B, T, L = self.B, self.T, self.L
        stop_wave = np.asarray(s_d)[:B]          # waves invoked per query
        if self.rng is None:
            predictions = np.asarray(pred_d, np.int64)[:B]
        else:
            beliefs = np.asarray(beliefs_d, np.float64)[:B]
            predictions, _ = tie_break_argmax(beliefs, self.rng)
        if self._failed is None:
            # fault-free fast path: unchanged pre-failover accounting
            sched_T = self.sched_T
            invoked_T = np.arange(T)[:, None] < stop_wave[None, :]
            costs = np.where(invoked_T, self.wc_T, 0.0).sum(axis=0)
            responses_T = np.where(invoked_T, self.resp_T, -1)
        else:
            # report the *effective* route — post-failover schedule, the
            # responses actually obtained, spend charged for the arms
            # actually invoked — so downstream accounting stays fault-blind
            src, valid = self._src, self._valid
            bb = np.broadcast_to(np.arange(B)[None, :], (T, B))
            sched_T = np.where(valid, self.sched_T[src, bb], -1)
            resp_eff = np.where(valid, self.resp_T[src, bb], -1)
            wc_eff = np.where(valid, self.wc_T[src, bb], 0.0)
            invoked_T = (
                np.arange(T)[:, None] < stop_wave[None, :]
            ) & (sched_T >= 0)
            if not self.router.failover:
                # frozen plans: a failed slot's wave still elapses, but the
                # arm never answered — not served, not charged
                invoked_T &= ~self._failed
            costs = np.where(invoked_T, wc_eff, 0.0).sum(axis=0)
            responses_T = np.where(invoked_T, resp_eff, -1)
        arm_query_counts = np.bincount(sched_T[invoked_T], minlength=L)
        return RouteResult(
            predictions=predictions,
            costs=costs,
            planned_costs=self.planned,
            clusters=self.cluster_ids,
            budgets=np.asarray(self.budgets),
            schedule=sched_T.T,
            responses=responses_T.T,
            invoked=invoked_T.T,
            arm_query_counts=arm_query_counts,
            waves=int(invoked_T.any(axis=1).sum()),
            **self._fault_kwargs(stop_wave),
        )

    # ------------------------------------------------------------------
    # reference kind: compacting wavefront, one step() per wave
    # ------------------------------------------------------------------
    def _prepare_reference_faults(self):
        """Mirror the jit plane's fault handling on the host wavefront.

        Same single host-side fault grid, same failover gather — but
        materialized into the plan tables up front (the compacting loop
        then runs unchanged over the *effective* plan), instead of gathered
        inside the device program. Computing the grid once on the original
        schedule is what keeps the two planes bit-identical under faults.
        """
        engine = self.router.engine
        codes, failed = engine.fault_grid(
            self.sched_T, row_offset=self.fault_row_offset
        )
        self._orig_sched_T = self.sched_T
        self._codes, self._failed = codes, failed
        self._rank = self._navail = None
        self._degrade_T = None
        if codes is None:
            return
        T, B = self.sched_T.shape
        degr = codes == FAULT_DEGRADE
        corrupt = None
        if degr.any():
            corrupt = np.where(
                degr,
                engine.fault_policy.corrupt_grid(
                    self.sched_T, row_offset=self.fault_row_offset
                ),
                -1,
            )
        if self.router.failover:
            src, valid, self._rank, self._navail = failover_gather(
                self.sched_T, failed
            )
            bb = np.broadcast_to(np.arange(B)[None, :], (T, B))
            self.sched_T = np.where(valid, self.sched_T[src, bb], -1)
            self.w_T = np.where(valid, self.w_T[src, bb], 0.0)
            self.res_T = np.where(valid, self.res_T[src, bb], -np.inf)
            self.wc_T = np.where(valid, self.wc_T[src, bb], 0.0)
            if corrupt is not None:
                self._degrade_T = np.where(valid, corrupt[src, bb], -1)
        else:
            self._degrade_T = corrupt

    def _init_reference(self):
        B, K = self.B, self.router.num_classes
        self.weights = self.w_T.T                # (B, T) view for the kernel
        self.resp_T = np.full((self.T, B), -1, np.int64)
        self.vote = np.zeros((B, K), np.float64)  # scatter-add log-weight table
        self.voted = np.zeros((B, K), bool)       # any vote -> real belief
        self.costs = np.zeros(B, np.float64)
        self.arm_query_counts = np.zeros(self.L, np.int64)
        self.cur = np.arange(B)                   # queries still in flight
        self.stop_at = np.full(B, self.T, np.int64)  # wave each query stopped
        self.waves = 0
        self._t = 0
        self._exhausted = False

    def _beliefs_rows(self, rows: np.ndarray) -> np.ndarray:
        router = self.router
        if router.use_kernel:
            # per-row independent contraction: feeding only in-flight rows
            # gives identical beliefs at a fraction of the kernel work
            return router._kernel_beliefs(
                np.ascontiguousarray(self.resp_T.T[rows]),
                self.weights[rows], self.empty[rows],
            )
        return np.where(
            self.voted[rows], self.vote[rows], self.empty[rows][:, None]
        )

    @property
    def exhausted(self) -> bool:
        """True once every query has left the wavefront (reference kind)."""
        return self.kind != "reference" or self._exhausted

    def step(self):
        """Advance the compacting wavefront one wave (reference kind only).

        Returns ``(rows, predictions)`` for the queries that *completed*
        this wave — their Prop. 4 stop fired, or the schedule ran out.
        ``predictions`` carries their final class ids when no tie-break rng
        is in play (a stopped query receives no further votes, so its
        argmax is already final); with an rng it is None and every
        prediction is drawn at finalization, preserving the one-shot path's
        rng stream. After exhaustion returns empty rows.
        """
        assert self.kind == "reference", "step() is for reference routes"
        if self._exhausted:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        with span("thrift.route.step"):
            return self._step()

    def _step(self):
        K = self.router.num_classes
        cur, t = self.cur, self._t
        bel = self._beliefs_rows(cur)
        if t >= self.T:
            # schedule exhausted: everything still in flight completes now
            self._exhausted = True
            self.cur = np.zeros(0, np.int64)
            preds = tie_break_argmax(bel)[0] if self.rng is None else None
            return cur, preds
        # Prop. 4 early-stop on the in-flight set, one mask per wave
        if K >= 2:
            part = np.partition(bel, K - 2, axis=1)
            h1, h2 = part[:, K - 1], part[:, K - 2]
        else:
            h1, h2 = bel[:, 0], np.full(cur.size, -np.inf)
        sched_t = self.sched_T[t]
        keep = (sched_t[cur] >= 0) & (
            self.res_T[t][cur] + h2 > h1 - self.stop_margin
        )
        stopped = cur[~keep]
        self.stop_at[stopped] = t
        preds = None
        if self.rng is None and stopped.size:
            preds = tie_break_argmax(bel[~keep])[0]
        elif self.rng is None:
            preds = np.zeros(0, np.int64)
        self.cur = cur = cur[keep]
        self._t = t + 1
        if cur.size == 0:
            self._exhausted = True
            return stopped, preds
        live = cur
        if self._failed is not None and not self.router.failover:
            # frozen plans under faults: the wave elapses for every in-flight
            # query, but failed arms are never invoked, charged, or counted
            live = cur[~self._failed[t][cur]]
        if live.size:
            self.waves += 1
            arms_t = sched_t[live]
            votes = self.router.engine.invoke_rows(arms_t, self.payloads, live)
            if self._degrade_T is not None:
                ov = self._degrade_T[t][live]
                votes = np.where(ov >= 0, ov, votes)
            self.arm_query_counts += np.bincount(arms_t, minlength=self.L)
            self.vote[live, votes] += self.w_T[t][live]
            self.voted[live, votes] = True
            self.costs[live] += self.wc_T[t][live]
            self.resp_T[t][live] = votes
        return stopped, preds

    def _finalize_reference(self) -> RouteResult:
        while not self._exhausted:
            self.step()
        with span("thrift.finalize"):
            return self._finalize_reference_host()

    def _finalize_reference_host(self) -> RouteResult:
        responses = np.ascontiguousarray(self.resp_T.T)
        if self.router.use_kernel:
            beliefs = self.router._kernel_beliefs(
                responses, self.weights, self.empty
            )
        else:
            beliefs = np.where(self.voted, self.vote, self.empty[:, None])
        predictions, _ = tie_break_argmax(beliefs, self.rng)
        invoked = responses >= 0
        return RouteResult(
            predictions=predictions,
            costs=self.costs,
            planned_costs=self.planned,
            clusters=self.cluster_ids,
            budgets=np.asarray(self.budgets),
            schedule=self.sched_T.T,
            responses=responses,
            invoked=invoked,
            arm_query_counts=self.arm_query_counts,
            waves=self.waves,
            **self._fault_kwargs(self.stop_at),
        )

    # ------------------------------------------------------------------
    def result(self) -> RouteResult:
        """Block until the route completes and return its RouteResult
        (cached — safe to call repeatedly)."""
        if self._result is None:
            self._result = (
                self._finalize_jit() if self.kind == "jit"
                else self._finalize_reference()
            )
        return self._result


class ThriftRouter:
    """Batched ThriftLLM serving router.

    Args:
      engine: arm pool executor.
      estimator: cluster -> p-hat success-probability estimator.
      num_classes: label-space size K.
      eps, delta, seed: SurGreedy Monte-Carlo parameters (paper Sec. 5).
      use_kernel: route belief aggregation through the ``belief_aggregate``
        Pallas kernel (float32 accumulation, dispatched from inside the
        jitted loop).
      jit_waves: run the wave loop as one on-device ``lax.scan``
        (:meth:`route_batch`); ``False`` falls back to the compacting
        host loop (:meth:`route_batch_reference`) which never invokes arms
        speculatively.
      failover: with an active engine fault policy, re-route a failed arm's
        wave slot to the plan's next-best affordable arm *inside* the wave
        program (both planes, identical semantics); ``False`` freezes the
        plan — failed slots simply lose their vote (and are not charged).
        Irrelevant (zero-cost identity) without injected faults.
      plan_service: optionally share a :class:`PlanService` across routers
        bound to the same pool; by default each router owns one.
    """

    def __init__(
        self,
        engine: PoolEngine,
        estimator: SuccessProbEstimator,
        num_classes: int,
        eps: float = 0.1,
        delta: float = 0.01,
        seed: int = 0,
        use_kernel: bool = False,
        jit_waves: bool = True,
        failover: bool = True,
        plan_service: Optional[PlanService] = None,
        donate_buffers: bool = True,
    ):
        self.engine = engine
        self.estimator = estimator
        self.num_classes = int(num_classes)
        self.use_kernel = bool(use_kernel)
        self.jit_waves = bool(jit_waves)
        self.failover = bool(failover)
        # Donate the packed wave buffer to XLA (`_wave_scan` vs its
        # `_nodonate` twin): bit-identical either way; off keeps the
        # transferred device buffers readable after dispatch (debugging).
        self.donate_buffers = bool(donate_buffers)
        # Optional device pin for the wave program. None (default) leaves
        # placement to JAX (the process default device). A ReplicaSet in
        # overlapped placement sets this per worker so each worker's wave
        # dispatches land on its own device and run concurrently; jit then
        # holds one executable per (bucket, device) pair, so prewarming
        # happens per pinned device (see ReplicaSet.prewarm_compile).
        self.device = None
        # host buffers placed for wave programs, and their bytes (padding
        # included): one per jit-plane group, plus prewarm's
        self.wave_uploads = 0
        self.wave_upload_bytes = 0
        self.selector = ThriftLLM(
            engine.costs, eps=eps, delta=delta, seed=seed, use_kernel=use_kernel
        )
        self.plans = plan_service or PlanService(
            self.selector, estimator, engine, self.num_classes
        )

    # ------------------------------------------------------------------
    # Planning: (cluster, budget) groups -> one cross-group wave schedule
    # ------------------------------------------------------------------
    def _group_plan(self, cid: int, budget: float) -> GroupPlan:
        return self.plans.plan(cid, budget)

    def _batch_plan(self, cluster_ids: np.ndarray, budgets: np.ndarray):
        """Merge per-group plans into batch-wide *wave-major* matrices.

        Groups are the unique (cluster, budget) pairs; the per-group plan
        rows are stacked once into (G, T) tables and expanded to the batch
        by a single gather on the group-inverse index. Returns
        ``(schedule (T, B), weights (T, B), residual (T, B),
        wave_costs (T, B), empty (B,), planned (B,))`` — wave-major so the
        hot paths touch contiguous (B,) rows per wave with no transposes.

        Heterogeneous-budget batches only; uniform budgets take the
        ``BatchTables`` fast path in :meth:`_plan_batch`."""
        b_vals, b_inv = np.unique(budgets, return_inverse=True)
        c_vals, c_inv = np.unique(cluster_ids, return_inverse=True)
        combo_vals, inverse = np.unique(
            c_inv * b_vals.size + b_inv, return_inverse=True
        )
        group_keys = [
            (int(c_vals[v // b_vals.size]), float(b_vals[v % b_vals.size]))
            for v in combo_vals
        ]
        plans = [self.plans.plan(c, b) for c, b in group_keys]
        order_m, fp_m, empty_v, planned_v = stack_plans(plans)
        fp_b = fp_m[:, :, inverse]                 # one gather for all floats
        return (
            order_m[:, inverse],
            fp_b[0],
            fp_b[1],
            fp_b[2],
            empty_v[inverse],
            planned_v[inverse],
        )

    def _plan_batch(self, embeddings: np.ndarray, budgets: np.ndarray):
        """Shared planning prologue of both batched paths.

        Uniform-budget batches (the common serving case) take the dense
        fast path: one nearest-centroid index lookup, one gather from the
        PlanService's cached :class:`~repro.serving.plans.BatchTables` —
        no ``np.unique``, no per-group Python. Heterogeneous budgets fall
        back to the generic group merge in :meth:`_batch_plan`.

        Returns ``(cluster_ids (B,), schedule (T, B), weights (T, B),
        residual (T, B), wave_costs (T, B), empty (B,), planned (B,))``.
        """
        if budgets[0] == budgets[-1] and (budgets == budgets[0]).all():
            idx = self.estimator.lookup_batch_indices(embeddings)
            cluster_ids = self.estimator.cluster_order[idx]
            tabs = self.plans.batch_tables(float(budgets[0]), idx=idx)
            fp = tabs.floats[:, :, idx]
            return (
                cluster_ids, tabs.order[:, idx], fp[0], fp[1], fp[2],
                tabs.empty[idx], tabs.planned[idx],
            )
        cluster_ids = self.estimator.lookup_batch(embeddings)
        return (cluster_ids,) + self._batch_plan(cluster_ids, budgets)

    def _empty_result(self, budgets: np.ndarray) -> RouteResult:
        return RouteResult(
            predictions=np.zeros(0, np.int64),
            costs=np.zeros(0, np.float64),
            planned_costs=np.zeros(0, np.float64),
            clusters=np.zeros(0, np.int64),
            budgets=np.asarray(budgets),
            schedule=np.full((0, 1), -1, np.int64),
            responses=np.full((0, 1), -1, np.int64),
            invoked=np.zeros((0, 1), bool),
            arm_query_counts=np.zeros(len(self.engine.arms), np.int64),
            waves=0,
        )

    # ------------------------------------------------------------------
    # Belief backend: float64 scatter tables or the Pallas kernel
    # ------------------------------------------------------------------
    def _kernel_beliefs(
        self, responses: np.ndarray, weights: np.ndarray, empty: np.ndarray
    ) -> np.ndarray:
        bel, _ = ops.belief_aggregate(
            jnp.asarray(responses, jnp.int32),
            jnp.asarray(weights, jnp.float32),
            jnp.asarray(empty, jnp.float32),
            self.num_classes,
        )
        return np.asarray(bel, np.float64)

    # ------------------------------------------------------------------
    # Cost metadata for the speculation switch
    # ------------------------------------------------------------------
    def speculation_cost(self, sched_T: np.ndarray, wc_T: np.ndarray) -> float:
        """Mean per-query USD the speculative all-cells gather would bill to
        *metered* arms over and above what any query could ever realize.

        The jitted path invokes every scheduled (query, wave) cell up front;
        the compacting reference only invokes waves the Prop. 4 stop rule
        lets run. The worst-case marginal exposure of speculating is
        therefore the full scheduled spend on metered arms (the realized
        part is paid either way; everything else is at risk of being pure
        waste). Unmetered arms (oracle / tabular / self-hosted) bill
        nothing real, so their speculative work is free throughput and
        contributes zero.
        """
        metered = self.engine.metered_mask
        if not metered.any():
            return 0.0
        billed = (sched_T >= 0) & metered[np.maximum(sched_T, 0)]
        return float(np.where(billed, wc_T, 0.0).sum() / max(sched_T.shape[1], 1))

    # ------------------------------------------------------------------
    # begin/step/finalize routing: the serving front-end's data plane
    # ------------------------------------------------------------------
    def begin_route(
        self,
        queries: Any,                    # arm-payloads, len B (array or list)
        embeddings: np.ndarray,          # (B, d)
        budget: Any,                     # scalar or (B,) per-query budgets
        stop_margin: float = STOP_MARGIN,
        rng: Optional[np.random.Generator] = None,
        mode: str = "auto",
        speculation_threshold: float = 0.0,
        fault_row_offset: int = 0,
    ) -> "PendingRoute":
        """Start routing a batch and return a :class:`PendingRoute` handle.

        This is the non-blocking half of :meth:`route_batch`: planning, the
        speculation-mode decision and (for the jitted mode) the speculative
        response gather + device dispatch all happen here; blocking
        finalization is deferred to ``PendingRoute.result()``. A serving
        front-end can therefore dispatch group *t+1* while group *t*'s
        jitted program is still running on device (double-buffered wave
        pipelining), or advance a reference-mode group wave by wave via
        ``PendingRoute.step()`` and complete per-query futures as each
        query's stop wave fires.

        Args:
          mode: ``"jit"`` forces the speculative jitted wave loop,
            ``"reference"`` the compacting host wavefront, and ``"auto"``
            — the cost-aware speculation switch — picks ``jit`` when
            :meth:`speculation_cost` (mean per-query USD at risk on metered
            arms) is at most ``speculation_threshold`` and falls back to
            ``reference`` for metered/expensive pools.
          speculation_threshold: USD per query the switch may gamble on
            speculative metered invocations. The default 0.0 speculates
            only when speculation is entirely free (no metered arm is
            scheduled).
          fault_row_offset: this batch's starting row inside a logically
            fused batch. A ReplicaSet dispatching the same admission wave
            as R overlapped per-device programs passes each worker's
            concatenation offset so fault draws (keyed on batch row) are
            bit-identical to the single fused dispatch.
        """
        B = len(queries)
        budgets = np.broadcast_to(np.asarray(budget, np.float64), (B,))
        if B == 0:
            return PendingRoute(self, "empty", result=self._empty_result(budgets))
        if mode not in ("auto", "jit", "reference"):
            raise ValueError(f"unknown route mode {mode!r}")
        with span("thrift.route.plan"):
            self.plans.refresh()
            (cluster_ids, sched_T, w_T, res_T, wc_T, empty,
             planned) = self._plan_batch(embeddings, budgets)
            spec_cost = self.speculation_cost(sched_T, wc_T)
            kind = mode
            if mode == "auto":
                # a router pinned to the reference plane (jit_waves=False —
                # the pre-metered-flag way to forbid speculation) keeps it
                # under auto, regardless of per-arm flags
                if not self.jit_waves or spec_cost > speculation_threshold:
                    kind = "reference"
                else:
                    kind = "jit"
            pending = PendingRoute(
                self, kind,
                budgets=budgets, cluster_ids=cluster_ids, sched_T=sched_T,
                w_T=w_T, res_T=res_T, wc_T=wc_T, empty=empty, planned=planned,
                payloads=self.engine.prepare_payloads(queries),
                stop_margin=float(stop_margin), rng=rng, spec_cost=spec_cost,
                plan_version=getattr(self.estimator, "plan_version", 0),
                fault_row_offset=fault_row_offset,
            )
        if kind == "jit":
            pending._dispatch_jit()
        return pending

    # ------------------------------------------------------------------
    def prewarm_compile(
        self,
        max_batch: int,
        max_waves: Optional[int] = None,
        all_batch_buckets: bool = False,
    ) -> int:
        """Pre-compile the jitted wave program ahead of traffic.

        Compiles every *wave-depth* bucket a plan could schedule (plans
        re-selected by online feedback may deepen across a bucket), at the
        batch bucket of ``max_batch`` — the bucket full admissions land in.
        Partial flushes and split budget groups land in *smaller* batch
        buckets; pass ``all_batch_buckets=True`` to compile those too (one
        program per (B, T) bucket pair — thorough, proportionally slower),
        as a serving replica taking ragged traffic should. ``max_waves``
        defaults to the pool size (no plan can schedule more arms than
        exist). Returns the number of bucket programs visited; no-op for
        routers pinned to the reference plane."""
        if not self.jit_waves:
            return 0
        if all_batch_buckets:
            b_buckets = sorted({
                _bucket(b, base=8) for b in range(1, max(1, int(max_batch)) + 1)
            })
        else:
            b_buckets = [_bucket(int(max_batch), base=8)]
        waves = int(max_waves) if max_waves is not None else len(self.engine.arms)
        t_buckets = sorted({_bucket(t, base=4) for t in range(1, max(1, waves) + 1)})
        # an all-unscheduled group of each bucket, through the same pack
        # and placement as traffic, so the warmed executable (per bucket
        # and pinned device) is exactly the one traffic hits
        for Bp in b_buckets:
            for Tp in t_buckets:
                none = np.full((Tp, Bp), -1, np.int32)
                self._run_wave(_pack_wave(
                    none, none, np.zeros((Tp, Bp)), np.full((Tp, Bp), -np.inf),
                    np.broadcast_to(np.arange(Tp)[:, None], (Tp, Bp)),
                    none >= 0, np.zeros(Bp), STOP_MARGIN,
                ))
        return len(b_buckets) * len(t_buckets)

    def _run_wave(self, packed: np.ndarray):
        """Place one :func:`_pack_wave` buffer and start its wave program;
        returns the program's three outputs, still on the device.

        Device pinning rides jax.default_device, not an explicit
        jax.device_put: committing the buffer per dispatch measured ~5x
        the whole dispatch cost on the CPU backend, while the context
        manager just steers where jit places the uncommitted numpy buffer
        and still caches one executable per (bucket, device). Placement
        stays inside the x64 context — a float64 buffer materialized
        outside it would silently downcast to float32."""
        self.wave_uploads += 1
        self.wave_upload_bytes += packed.nbytes
        ctx = (
            jax.default_device(self.device)
            if self.device is not None else contextlib.nullcontext()
        )
        scan_fn = _wave_scan if self.donate_buffers else _wave_scan_nodonate
        with x64(), ctx, _quiet_donation():
            return scan_fn(
                packed, num_classes=self.num_classes, use_kernel=self.use_kernel
            )

    def upload_stats(self) -> dict:
        """Wave-program upload counters (mirrored into
        ``BatchScheduler.stats``): host buffers placed and their bytes."""
        return {
            "wave_uploads": self.wave_uploads,
            "wave_upload_bytes": self.wave_upload_bytes,
        }

    # ------------------------------------------------------------------
    def route_batch(
        self,
        queries: Any,                    # arm-payloads, len B (array or list)
        embeddings: np.ndarray,          # (B, d)
        budget: Any,                     # scalar or (B,) per-query budgets
        stop_margin: float = STOP_MARGIN,
        rng: Optional[np.random.Generator] = None,
    ) -> RouteResult:
        """Route a batch end to end: cluster lookup, plan-cache gather, one
        on-device wave loop, host-side finalization.

        With ``jit_waves=True`` (default) every scheduled (query, wave)
        response is fetched in a single heterogeneous engine call and the
        whole adaptive loop runs as one jitted program; with
        ``jit_waves=False`` this delegates to the compacting
        :meth:`route_batch_reference`. Both return identical
        predictions/costs/arms-used for deterministic arm pools. The
        synchronous convenience wrapper over :meth:`begin_route` +
        ``PendingRoute.result()``.

        Args:
          queries: per-arm payloads (tokens, (cluster, label) pairs, ...).
          embeddings: (B, d) query embeddings for cluster lookup.
          budget: scalar or (B,) per-query USD budgets.
          stop_margin: Prop. 4 slack; keep the default for paper semantics.
          rng: optional generator for belief-tie breaking (None = argmax).
        """
        mode = "jit" if self.jit_waves else "reference"
        return self.begin_route(
            queries, embeddings, budget, stop_margin=stop_margin, rng=rng,
            mode=mode,
        ).result()

    # ------------------------------------------------------------------
    def route_batch_reference(
        self,
        queries: Any,
        embeddings: np.ndarray,
        budget: Any,
        stop_margin: float = STOP_MARGIN,
        rng: Optional[np.random.Generator] = None,
    ) -> RouteResult:
        """Compacting host-side wavefront (the PR 1 engine) — the semantics
        reference the jitted :meth:`route_batch` is equivalence-tested
        against, and the production path for pools where speculative
        invocation costs real money.

        Stopped queries are dropped from the in-flight index set each wave,
        so wave t only touches (and only *invokes*) the queries still in
        flight; belief state is a float64 (B, K) scatter table (or the
        Pallas kernel under ``use_kernel=True``). Implemented as
        :meth:`begin_route` with ``mode="reference"`` stepped to
        completion.
        """
        return self.begin_route(
            queries, embeddings, budget, stop_margin=stop_margin, rng=rng,
            mode="reference",
        ).result()

    # ------------------------------------------------------------------
    def route_batch_sequential(
        self,
        queries: Any,
        embeddings: np.ndarray,
        budget: Any,
        rng: Optional[np.random.Generator] = None,
    ) -> RouteResult:
        """Sequential oracle: one ``adaptive_invoke`` per query.

        The per-query semantics source both batched paths are
        equivalence-tested against (``tests/test_router_batched.py``) and
        the baseline of the serving throughput benchmark. Shares the plan
        service's selection cache, so all paths route the same selected
        sets.

        Exact output equality with :meth:`route_batch` holds for
        *deterministic* arms (responses a pure function of (arm, query),
        e.g. the test TabularArm or LMArm). Stochastic ``OracleArm`` pools
        consume different rng streams on the batched paths (pooled
        ``invoke_rows`` draws vs per-arm draws here), so per-seed
        realizations differ even though the distributions match.
        """
        B = len(queries)
        K = self.num_classes
        budgets = np.broadcast_to(np.asarray(budget, np.float64), (B,))
        self.plans.refresh()
        cluster_ids = self.estimator.lookup_batch(embeddings)
        L = len(self.engine.arms)

        predictions = np.zeros(B, np.int64)
        costs = np.zeros(B, np.float64)
        planned = np.zeros(B, np.float64)
        arms_used: List[List[int]] = []
        resp_rows: List[np.ndarray] = []
        arm_query_counts = np.zeros(L, np.int64)
        for j in range(B):
            p = self.estimator.clusters[int(cluster_ids[j])].p_hat
            sel = self.selector.select(p, K, float(budgets[j]))

            def invoke_one(arm: int) -> int:
                mask = np.zeros(B, bool)
                mask[j] = True
                return int(self.engine.invoke_arm(int(arm), queries, mask)[j])

            inv = adaptive_invoke(
                list(sel.chosen), p, K, invoke_one, rng=rng, costs=self.engine.costs
            )
            predictions[j] = inv.prediction
            costs[j] = inv.cost
            planned[j] = inv.planned_cost
            arms_used.append([int(a) for a in inv.used])
            resp_rows.append(np.asarray(inv.responses, np.int64))
            arm_query_counts[inv.used] += 1
        T = max(1, max((len(a) for a in arms_used), default=1))
        schedule = np.full((B, T), -1, np.int64)
        responses = np.full((B, T), -1, np.int64)
        invoked = np.zeros((B, T), bool)
        for j, used in enumerate(arms_used):
            schedule[j, : len(used)] = used
            responses[j, : len(used)] = resp_rows[j]
            invoked[j, : len(used)] = True
        res = RouteResult(
            predictions=predictions,
            costs=costs,
            planned_costs=planned,
            clusters=cluster_ids,
            budgets=np.asarray(budgets),
            schedule=schedule,
            responses=responses,
            invoked=invoked,
            arm_query_counts=arm_query_counts,
            waves=T,
        )
        res._arms_used = arms_used
        return res
