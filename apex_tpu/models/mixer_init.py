"""Initialisers of the recurrent mixers' own leaves (Kimi Delta
Attention's and Mamba-2's): the decay's ``A_log``, the step's
``dt_bias`` and the short convolution's weight and bias."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def a_log_init(key, shape, dtype=jnp.float32):
    """log U[1, 16] (FLA's KDA, mamba_ssm's Mamba-2)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32, dt_min=1e-3, dt_max=0.1,
                 floor=1e-4):
    """softplus^-1 of a step log-uniform in [dt_min, dt_max], floored at
    ``floor``: softplus(dt_bias) is the decay rate's initial scale."""
    u = jax.random.uniform(key, shape, dtype)
    dt = jnp.maximum(jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                             + math.log(dt_min)), floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def conv_init(key, shape, dtype=jnp.float32):
    """torch's Conv1d default for a depthwise kernel (K taps, channels):
    U(-K^-1/2, K^-1/2)."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def conv_bias_init(key, shape, dtype=jnp.float32, taps=4):
    """torch's Conv1d default bias for a depthwise kernel of ``taps``:
    U(-taps^-1/2, taps^-1/2)."""
    bound = taps ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)
