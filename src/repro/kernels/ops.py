"""Jit'd public wrappers for the Pallas kernels.

The platform decides how a kernel runs: on a CPU backend it runs in
``interpret=True`` mode (the kernel body executes per grid step as plain
JAX), on a TPU it is lowered natively, never interpreted. The wrappers
handle padding/layout so call sites never see tiling constraints.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .belief_aggregate import belief_aggregate_pallas
from .flash_attention import flash_attention_pallas
from .mc_correctness import mc_correctness_grouped_pallas, mc_correctness_pallas
from .rglru_scan import rglru_scan_pallas


def _interpret() -> bool:
    """Pallas has no CPU lowering: interpret there, and only there."""
    return jax.default_backend() == "cpu"


def mc_correctness(responses, masks, log_weights, empty_belief, num_classes: int):
    """(C,) Monte-Carlo xi estimates over shared response draws."""
    return mc_correctness_pallas(
        responses, masks, log_weights, empty_belief, num_classes,
        interpret=_interpret(),
    )


def mc_correctness_grouped(responses, masks, log_weights, empty_belief,
                           valid, theta, num_classes: int, tile: int = 256):
    """(G, C) xi estimates over the batched planner's stacked (G, theta, L)
    draws; ragged thetas carried by the ``valid`` mask."""
    return mc_correctness_grouped_pallas(
        responses, masks, log_weights, empty_belief, valid, theta,
        num_classes, tile=tile, interpret=_interpret(),
    )


def belief_aggregate(responses, log_weights, empty_belief, num_classes: int,
                     tile: int = 128):
    """Batched router aggregation: (log_beliefs (B,K), predictions (B,)).

    Safe to call from inside traced/jitted code (the serving router
    dispatches it from the jitted wave program); ``tile`` trades grid steps
    for VMEM footprint and does not affect per-row results.
    """
    return belief_aggregate_pallas(
        responses, log_weights, empty_belief, num_classes, tile=tile,
        interpret=_interpret(),
    )


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512):
    """(B,S,H,hd) x (B,T,G,hd) -> (B,S,H,hd) with causal block skipping."""
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, interpret=_interpret(),
    )


def rglru_scan(log_a, gated, h0):
    """Diagonal linear recurrence: (h (B,S,D), h_last (B,D))."""
    return rglru_scan_pallas(
        jnp.asarray(log_a, jnp.float32),
        jnp.asarray(gated, jnp.float32),
        jnp.asarray(h0, jnp.float32),
        interpret=_interpret(),
    )


def mamba_scan(x, dt, A, Bmat, Cmat, Dskip, h0):
    """Fused Mamba-1 selective scan: (y (B,S,Din), h_last (B,Din,N))."""
    from .mamba_scan import mamba_scan_pallas

    f32 = lambda t: jnp.asarray(t, jnp.float32)
    return mamba_scan_pallas(
        f32(x), f32(dt), f32(A), f32(Bmat), f32(Cmat), f32(Dskip), f32(h0),
        interpret=_interpret(),
    )
