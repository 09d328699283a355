"""Pure-jnp oracles for the generalized fused local step.

    v = c_g·g + c_x·x + Σ_j c_j·aux_j
    x_new = x − η_l·v

with coefs = (η_l, c_g, c_x, c_aux...) exactly as the kernel consumes them
(``fed_direction_ref``), and FedCM's blend written out as Algorithm 2
line 8–9 (``fedcm_step_ref``), which pins fedcm's direction row
independently of the generic form.
"""
from __future__ import annotations

import jax.numpy as jnp


def fed_direction_ref(x, g, auxes, coefs):
    coefs = coefs.astype(jnp.float32)
    v = coefs[1] * g.astype(jnp.float32) + coefs[2] * x.astype(jnp.float32)
    for j, a in enumerate(auxes):
        v = v + coefs[3 + j] * a.astype(jnp.float32)
    return (x.astype(jnp.float32) - coefs[0] * v).astype(x.dtype)


def fedcm_step_ref(x, g, delta, alpha, eta_l):
    """FedCM's client step: ``v = α·g + (1−α)·Δ``, ``x_new = x − η_l·v``."""
    v = alpha * g.astype(jnp.float32) + (1.0 - alpha) * delta.astype(jnp.float32)
    return (x.astype(jnp.float32) - eta_l * v).astype(x.dtype)
