"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.x64 import x64

from repro.core.mc import xi_from_responses, xi_from_responses_grouped
from repro.core.belief import aggregate_log_beliefs_batch
from repro.models.attention import blocked_attention, direct_attention


def mc_correctness_ref(responses, masks, log_weights, empty_belief, num_classes):
    """(C,) xi estimates — delegates to the core estimator math."""
    return xi_from_responses(
        responses, masks, log_weights, jnp.float32(empty_belief), num_classes
    )


def mc_correctness_grouped_ref(responses, masks, log_weights, empty_belief,
                               valid, theta, num_classes):
    """(G, C) xi estimates — delegates to the batched planner's bit-stable
    grouped core (f64 out; compare with a float32 tolerance)."""
    with x64():
        vals = xi_from_responses_grouped(
            responses, masks, log_weights, empty_belief, valid,
            jnp.asarray(theta, jnp.float64), num_classes=num_classes,
        )
    return vals.astype(jnp.float32)


def belief_aggregate_ref(responses, log_weights, empty_belief, num_classes):
    """Returns (log_beliefs (B, K), predictions (B,)); ``empty_belief`` may
    be a scalar or a (B,) per-row vector."""
    beliefs = aggregate_log_beliefs_batch(
        responses, log_weights, num_classes, jnp.asarray(empty_belief, jnp.float32)
    )
    return beliefs, jnp.argmax(beliefs, axis=-1).astype(jnp.int32)


def flash_attention_ref(q, k, v, causal=True, window=0):
    """Direct softmax attention in fp32 (no blocking)."""
    return direct_attention(q, k, v, causal=causal, window=window)


def rglru_scan_ref(log_a, gated, h0):
    """Sequential reference for the diagonal recurrence."""

    def step(h, xs):
        la, u = xs
        h = jnp.exp(la) * h + u
        return h, h

    h_last, hs = jax.lax.scan(
        step, h0, (log_a.transpose(1, 0, 2), gated.transpose(1, 0, 2))
    )
    return hs.transpose(1, 0, 2), h_last


def mamba_scan_ref(x, dt, A, Bmat, Cmat, Dskip, h0):
    """Delegates to the model substrate's chunked selective scan."""
    from repro.models.ssm import selective_scan

    y, h_last = selective_scan(x, dt, A, Bmat, Cmat, Dskip, h0=h0, chunk=64)
    return y.astype(jnp.float32), h_last
