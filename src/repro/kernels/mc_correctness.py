"""Pallas TPU kernel: Monte-Carlo correctness-probability estimation.

The selector's hot spot (paper Section 4.3): evaluate xi-hat for C candidate
subsets over theta shared response draws. Reformulated for the MXU as a
one-hot contraction per theta-tile:

    beliefs[c, t, k] = sum_l (mask[c,l] * w[l]) * onehot(resp[t,l])[k]

Grid: one dimension over theta tiles; every tile accumulates its partial
fractional-credit sums into the (C,) output block (TPU sequential-grid
revisiting pattern; the first tile initializes). VMEM residency per tile:
the (Tt, L, K) one-hot cube + the (C, L) mask matrix; Tt is chosen so the
cube fits comfortably (Tt=256, L<=32, K<=128 -> 4 MB fp32).

``ref.py:mc_correctness_ref`` is the pure-jnp oracle (same math as
``repro.core.mc.xi_from_responses``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TIE_TOL = 1e-6


def _kernel(resp_ref, maskw_ref, mask_ref, empty_ref, out_ref, *, num_classes, theta_total):
    """One theta-tile.

    resp_ref:  (Tt, L) int32 responses (ground truth = class 0)
    maskw_ref: (C, L) f32 mask * log-weight
    mask_ref:  (C, L) f32 subset indicator
    empty_ref: (1, 1) f32 empty-class log belief
    out_ref:   (1, C) f32 accumulated xi estimates
    """
    i = pl.program_id(0)

    resp = resp_ref[...]                                   # (Tt, L)
    Tt, L = resp.shape
    K = num_classes

    # one-hot cube via iota comparison: (Tt, L, K)
    classes = jax.lax.broadcasted_iota(jnp.int32, (Tt, L, K), 2)
    onehot = (resp[:, :, None] == classes).astype(jnp.float32)

    maskw = maskw_ref[...]                                 # (C, L)
    mask = mask_ref[...]
    flat = onehot.transpose(1, 0, 2).reshape(L, Tt * K)    # (L, Tt*K)
    # beliefs/counts: (C, Tt, K) — contraction over L lowers to MXU dots
    dn = (((1,), (0,)), ((), ()))
    beliefs = jax.lax.dot_general(
        maskw, flat, dn, preferred_element_type=jnp.float32
    ).reshape(-1, Tt, K)
    counts = jax.lax.dot_general(
        mask, flat, dn, preferred_element_type=jnp.float32
    ).reshape(-1, Tt, K)

    empty = empty_ref[0, 0]
    beliefs = jnp.where(counts > 0, beliefs, empty)

    mx = jnp.max(beliefs, axis=-1, keepdims=True)
    is_max = (beliefs >= mx - TIE_TOL).astype(jnp.float32)
    ties = jnp.sum(is_max, axis=-1)                        # (C, Tt)
    credit = is_max[:, :, 0] / ties
    partial = jnp.sum(credit, axis=-1) / theta_total       # (C,)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0, :] += partial


@functools.partial(
    jax.jit, static_argnames=("num_classes", "tile", "interpret")
)
def mc_correctness_pallas(
    responses: jnp.ndarray,    # (theta, L) int32
    masks: jnp.ndarray,        # (C, L) float32
    log_weights: jnp.ndarray,  # (L,) float32
    empty_belief: jnp.ndarray, # scalar f32
    num_classes: int,
    tile: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    theta, L = responses.shape
    C = masks.shape[0]
    tile = min(tile, theta)
    n = (theta + tile - 1) // tile
    pad = n * tile - theta
    if pad:  # padded rows: response -1 matches no class -> all-empty -> 1/K
        responses = jnp.concatenate(
            [responses, jnp.full((pad, L), -1, jnp.int32)], axis=0
        )
    maskw = masks * log_weights[None, :]
    empty = jnp.asarray(empty_belief, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, num_classes=num_classes, theta_total=float(theta)),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((tile, L), lambda i: (i, 0)),
            pl.BlockSpec((C, L), lambda i: (0, 0)),
            pl.BlockSpec((C, L), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.float32),
        interpret=interpret,
    )(responses, maskw, masks, empty)
    # padded rows contributed 1/K each (all-empty tie credit); subtract
    correction = pad * (1.0 / num_classes) / float(theta)
    return out[0] - correction


# ---------------------------------------------------------------------------
# Grouped-mask layout: the batched planner's (G, theta, L) draws
# ---------------------------------------------------------------------------


def _grouped_kernel(resp_ref, maskw_ref, mask_ref, empty_ref, valid_ref,
                    theta_ref, out_ref, *, num_classes):
    """One (group, theta-tile) cell. Every block's last two dims are the
    array's own (or 8x128-aligned), as the TPU compiler requires.

    resp_ref:  (1, Tt, L) int32 responses of the cell's group
    maskw_ref: (1, C, L) f32 mask * log-weight
    mask_ref:  (1, C, L) f32 subset indicator
    empty_ref: (1, 1, 1) f32 empty-class log belief
    valid_ref: (1, 1, Tt) f32 draw mask (0 past the group's own theta)
    theta_ref: (1, 1, 1) f32 the group's real draw count
    out_ref:   (1, C, 1) f32 accumulated xi estimates (revisited over tiles)
    """
    i = pl.program_id(1)

    resp = resp_ref[0]                                     # (Tt, L)
    maskw = maskw_ref[0]                                   # (C, L)
    mask = mask_ref[0]
    empty = empty_ref[0]                                   # (1, 1)

    # per class k: (C, L) x (Tt, L)^T -> (C, Tt) beliefs on the MXU, at
    # full float32 precision (the one-hots are exact; the weights are not)
    dn = (((1,), (1,)), ((), ()))
    hp = jax.lax.Precision.HIGHEST
    disp = []
    for k in range(num_classes):
        onehot = (resp == k).astype(jnp.float32)           # (Tt, L)
        bel = jax.lax.dot_general(maskw, onehot, dn, precision=hp,
                                  preferred_element_type=jnp.float32)
        cnt = jax.lax.dot_general(mask, onehot, dn, precision=hp,
                                  preferred_element_type=jnp.float32)
        disp.append(jnp.where(cnt > 0, bel, empty))

    mx = disp[0]
    for d in disp[1:]:
        mx = jnp.maximum(mx, d)
    thr = mx - TIE_TOL
    ties = jnp.zeros_like(mx)
    for d in disp:
        ties = ties + (d >= thr).astype(jnp.float32)
    credit = (disp[0] >= thr).astype(jnp.float32) / ties * valid_ref[0]
    partial = jnp.sum(credit, axis=-1, keepdims=True) / theta_ref[0]  # (C, 1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += partial


@functools.partial(
    jax.jit, static_argnames=("num_classes", "tile", "interpret")
)
def mc_correctness_grouped_pallas(
    responses: jnp.ndarray,    # (G, theta, L) int32, -1 = padded draw
    masks: jnp.ndarray,        # (G, C, L) float32
    log_weights: jnp.ndarray,  # (G, L) float32
    empty_belief: jnp.ndarray, # (G,) f32
    valid: jnp.ndarray,        # (G, theta) f32 draw mask
    theta: jnp.ndarray,        # (G,) f32 real draw counts
    num_classes: int,
    tile: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """Grouped-mask xi estimation: grid (G, theta tiles), each group row
    accumulated independently. The ragged-theta layout is explicit — padded
    draws carry ``valid`` 0 and contribute nothing, so no post-hoc padding
    correction is needed (unlike the ungrouped kernel). Same flag
    conventions as ``belief_aggregate``: ``interpret`` on CPU, ``tile``
    trades grid steps for VMEM with no effect on results. The per-group
    operands ride 3-D layouts so that each block's last two dims equal the
    array's."""
    G, theta_n, L = responses.shape
    C = masks.shape[1]
    tile = min(tile, theta_n)
    n = (theta_n + tile - 1) // tile
    pad = n * tile - theta_n
    if pad:
        responses = jnp.concatenate(
            [responses, jnp.full((G, pad, L), -1, jnp.int32)], axis=1
        )
        valid = jnp.concatenate(
            [valid, jnp.zeros((G, pad), jnp.float32)], axis=1
        )
    maskw = masks * log_weights[:, None, :]
    empty = jnp.asarray(empty_belief, jnp.float32).reshape(G, 1, 1)
    theta = jnp.asarray(theta, jnp.float32).reshape(G, 1, 1)
    valid = jnp.asarray(valid, jnp.float32).reshape(G, 1, n * tile)

    out = pl.pallas_call(
        functools.partial(_grouped_kernel, num_classes=num_classes),
        grid=(G, n),
        in_specs=[
            pl.BlockSpec((1, tile, L), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, C, L), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, C, L), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, 1, tile), lambda g, i: (g, 0, i)),
            pl.BlockSpec((1, 1, 1), lambda g, i: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, 1), lambda g, i: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, C, 1), jnp.float32),
        interpret=interpret,
    )(responses, maskw, masks, empty, valid, theta)
    return out[:, :, 0]
