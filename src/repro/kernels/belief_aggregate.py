"""Pallas TPU kernel: batched belief aggregation for the serving router.

For a batch of requests, combine per-arm responses into per-class log
beliefs (paper Eq. 4) and the argmax prediction:

    beliefs[b, k] = sum_m w[b, m] * onehot(resp[b, m])[k]   (empty -> const)

Grid over request tiles. The contraction over the M arms is an unrolled
multiply-and-sum of (Bt, K) slabs, in ascending arm order: every operand is
2-D, which the TPU compiler needs, and the float32 sum order does not depend
on which other arms are masked. Arms flagged -1 are masked (not invoked for
that request — adaptive early-stopped wavefronts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(resp_ref, w_ref, empty_ref, bel_ref, pred_ref, *, num_classes):
    resp = resp_ref[...]                                    # (Bt, M) int32
    Bt, M = resp.shape
    K = num_classes
    w = w_ref[...]                                          # (Bt, M)

    classes = jax.lax.broadcasted_iota(jnp.int32, (Bt, K), 1)
    beliefs = jnp.zeros((Bt, K), jnp.float32)
    seen = jnp.zeros((Bt, K), jnp.bool_)
    for m in range(M):                   # -1 responses match no class
        hit = resp[:, m:m + 1] == classes                   # (Bt, K)
        beliefs = beliefs + jnp.where(hit, w[:, m:m + 1], 0.0)
        seen = seen | hit
    empty = empty_ref[...]                                  # (Bt, 1) per-row
    beliefs = jnp.where(seen, beliefs, empty)
    bel_ref[...] = beliefs
    # first-max argmax as a max and a min, both plain lane reductions
    mx = jnp.max(beliefs, axis=-1, keepdims=True)
    pred_ref[...] = jnp.min(
        jnp.where(beliefs == mx, classes, K), axis=-1, keepdims=True
    )


@functools.partial(jax.jit, static_argnames=("num_classes", "tile", "interpret"))
def belief_aggregate_pallas(
    responses: jnp.ndarray,    # (B, M) int32, -1 = not invoked
    log_weights: jnp.ndarray,  # (B, M) or (M,) float32
    empty_belief: jnp.ndarray, # scalar or (B,) per-row empty-class belief
    num_classes: int,
    tile: int = 128,
    interpret: bool = True,
):
    """Returns (log_beliefs (B, K), predictions (B,))."""
    B, M = responses.shape
    w = jnp.asarray(log_weights, jnp.float32)
    if w.ndim == 1:
        w = jnp.broadcast_to(w[None, :], (B, M))
    empty = jnp.asarray(empty_belief, jnp.float32)
    if empty.ndim == 0:
        empty = jnp.broadcast_to(empty, (B,))
    tile = min(tile, B)
    n = (B + tile - 1) // tile
    pad = n * tile - B
    if pad:
        responses = jnp.concatenate(
            [responses, jnp.full((pad, M), -1, jnp.int32)], axis=0
        )
        w = jnp.concatenate([w, jnp.zeros((pad, M), jnp.float32)], axis=0)
        empty = jnp.concatenate([empty, jnp.zeros(pad, jnp.float32)])
    empty = empty[:, None]

    beliefs, preds = pl.pallas_call(
        functools.partial(_kernel, num_classes=num_classes),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((tile, M), lambda i: (i, 0)),
            pl.BlockSpec((tile, M), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, num_classes), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n * tile, num_classes), jnp.float32),
            jax.ShapeDtypeStruct((n * tile, 1), jnp.int32),
        ],
        interpret=interpret,
    )(responses, w, empty)
    return beliefs[:B], preds[:B, 0]
