"""The short depthwise causal convolution and SiLU that recurrent mixers
put in front of their scans (Kimi Delta Attention's q, k and v; Mamba-2's
x, B and C), float32 inside, under the calling mixer's scope."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def short_conv(x, w, bias=None, *, scope):
    """SiLU of a depthwise causal convolution over time: x (B, S, C),
    w (K, C), bias (C,) or None -> (B, S, C) in x's dtype,
    y_t = silu(sum_j w_j x_{t - K + 1 + j} + bias), float32 inside,
    under ``scope`` (the caller's mixer's)."""
    with jax.named_scope(scope):
        kk, s = w.shape[0], x.shape[1]
        xp = jnp.pad(x.astype(F32), ((0, 0), (kk - 1, 0), (0, 0)))
        y = sum(w[j].astype(F32) * xp[:, j:j + s]
                for j in range(kk))
        if bias is not None:
            y = y + bias.astype(F32)
        return jax.nn.silu(y).astype(x.dtype)
