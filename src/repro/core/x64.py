"""The float64 decision, in one place.

The planner (`_sur_greedy_scan`) and the wave program (`_wave_scan`) run in
float64 so that they match their host references (serial ``sur_greedy``,
``route_batch_reference``) bit for bit. Every site that builds or calls
one of them enters this context.
"""
from __future__ import annotations

import jax


def x64():
    """Context manager under which JAX arrays default to 64-bit types."""
    return jax.enable_x64(True)
