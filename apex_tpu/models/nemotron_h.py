"""Hybrid Mamba-2 / expert decoder (Nemotron-H's ``nemotron_h`` layer
stack): every layer is ONE pre-norm block of three kinds, read from a
pattern string (``M`` a Mamba-2 mixer, ``E`` an expert layer, ``*``
attention), added to the residual stream.

    h' = h + Block( RMSNorm(h) )
    loss = mean cross-entropy( W_head RMSNorm_f(h_L) )

The Mamba-2 mixer (``ops/ssd.py``), H heads of P channels, G groups of
a state of N:

    [z | xBC | dt] = x W_in                  (H P, H P + 2 G N, H)
    xBC = SiLU(conv_K(xBC) + b)              (depthwise, causal)
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    y = W_out [ RMSNorm_groups(y * SiLU(z)) * w ]   (groups of H P / G)

Attention: grouped-query causal softmax attention without positions
(``flash_attention``, fewer key/value heads than query heads).

Experts (``transformer/moe.py:DroplessMoE`` with ``activation``
``"relu2"``): s = sigmoid(x W_r) over ALL ``num_experts``, the top-k of
s + b, gates renormalised and times ``routed_scale``; non-gated experts
down(relu(up x)^2), THIS holder's ``experts_held`` from
``expert_offset``, and one shared expert of the same form.

Every layer is rematerialised (its input is all it keeps); expert
layers return their held experts' token counts.  Scopes: ``apex_ssd/
{conv,dt,scan,gate_norm}`` (the scan's kernels ``apex_ssd_fwd`` /
``apex_ssd_bwd``), ``apex_attention``, ``apex_moe/{router,dispatch,
experts,combine,shared}``, ``apex_linear`` (docs/observability.md).
Layout (b, s, h) between layers.  Written for tp=1.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.mixer_init import (a_log_init, conv_bias_init, conv_init,
                                       dt_bias_init)
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.short_conv import short_conv
from apex_tpu.ops.ssd import chunk_ssd
from apex_tpu.ops.xentropy import softmax_cross_entropy
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.moe import DroplessMoE

_STD = 0.02
_INIT = nn.initializers.normal(_STD)
# unit scale for the token table, as the other expert decoders' and for
# their reason (models/sparse_moe.py): at 0.02 every token routes alike
_EMBED_INIT = nn.initializers.normal(1.0)
F32 = jnp.float32


def _column(inp, out, name, dtype, init=_INIT):
    return tp.ColumnParallelLinear(inp, out, bias=False, gather_output=False,
                                   init_method=init, compute_dtype=dtype,
                                   name=name)


def _row(inp, out, name, dtype, init=_INIT):
    return tp.RowParallelLinear(inp, out, bias=False, input_is_parallel=True,
                                init_method=init, compute_dtype=dtype,
                                name=name)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer over a normed input (b, s, h) -> (b, s, h)."""
    hidden_size: int
    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_size: int = 4
    eps: float = 1e-5
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    out_scale: float = 1.0              # out_proj's, of the normal(0.02)
    dtype: jnp.dtype = F32

    @nn.compact
    def __call__(self, x):
        h, nh, p, dt = self.hidden_size, self.num_heads, self.head_dim, \
            self.dtype
        g, n = self.n_groups, self.state_size
        b, s, _ = x.shape
        inner = nh * p
        conv_dim = inner + 2 * g * n
        zxd = _column(h, inner + conv_dim + nh, "in_proj", dt)(x)
        w = self.param("conv_weight", conv_init, (self.conv_size, conv_dim),
                       F32)
        bias = self.param("conv_bias", functools.partial(
            conv_bias_init, taps=self.conv_size), (conv_dim,), F32)
        with jax.named_scope("apex_ssd/conv"):
            z, xbc, dtr = jnp.split(zxd, [inner, inner + conv_dim], axis=-1)
            xbc = short_conv(xbc, w, bias, scope="apex_ssd/conv")
            xs, B, C = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            xs = xs.reshape(b, s, nh, p)
            B, C = B.reshape(b, s, g, n), C.reshape(b, s, g, n)
        dt_bias = self.param("dt_bias", functools.partial(
            dt_bias_init, dt_min=self.time_step_min, dt_max=self.time_step_max,
            floor=self.time_step_floor), (nh,), F32)
        a_log = self.param("A_log", a_log_init, (nh,), F32)
        d = self.param("D", nn.initializers.ones, (nh,), F32)
        with jax.named_scope("apex_ssd/dt"):
            step = jax.nn.softplus(dtr.astype(F32) + dt_bias)
            a = -jnp.exp(a_log)
        y = chunk_ssd(xs, step, a, B, C)
        with jax.named_scope("apex_ssd/scan"):          # the D skip
            y = y.astype(F32) + d[:, None] * xs.astype(F32)
        w_norm = self.param("gate_norm", nn.initializers.ones, (inner,), F32)
        with jax.named_scope("apex_ssd/gate_norm"):
            y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(F32))
            yg = y.reshape(b, s, g, inner // g)
            yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                    + self.eps)
            y = yg.reshape(b, s, inner) * w_norm
        return _row(inner, h, "out_proj", dt,
                    nn.initializers.normal(_STD * self.out_scale))(
                        y.astype(dt))


class GQAttention(nn.Module):
    """Causal grouped-query attention without positions, no biases."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    out_scale: float = 1.0              # o_proj's, of the normal(0.02)
    dtype: jnp.dtype = F32

    @nn.compact
    def __call__(self, x):
        h, nh, kvh, d, dt = (self.hidden_size, self.num_heads,
                             self.num_kv_heads, self.head_dim, self.dtype)
        b, s, _ = x.shape
        q = _column(h, nh * d, "q_proj", dt)(x)
        k = _column(h, kvh * d, "k_proj", dt)(x)
        v = _column(h, kvh * d, "v_proj", dt)(x)
        with jax.named_scope("apex_attention"):     # and the layout changes
            o = flash_attention(*(jnp.swapaxes(t.reshape(b, s, -1, d), 1, 2)
                                  for t in (q, k, v)), causal=True)
            o = jnp.swapaxes(o, 1, 2).reshape(b, s, nh * d)
        return _row(nh * d, h, "o_proj", dt,
                    nn.initializers.normal(_STD * self.out_scale))(o)


class NemotronHLayer(nn.Module):
    """One block -> (h, counts): ``counts`` (experts_held,) the tokens
    routed to each held expert, None for a mixer.  ``kind`` is the
    pattern's letter; ``out_scale`` scales the normal(0.02) of the
    matrix that writes the residual stream (``out_proj``, ``o_proj``,
    the experts' and the shared expert's ``down``)."""
    kind: str
    hidden_size: int
    mamba: dict
    attention: dict
    experts: dict
    eps: float
    out_scale: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h, dt, out = self.hidden_size, self.dtype, self.out_scale
        b, s, _ = x.shape
        xn = FusedRMSNorm(normalized_shape=h, eps=self.eps,
                          name="norm")(x).astype(dt)
        if self.kind == "M":
            y, counts = Mamba2Mixer(h, eps=self.eps, out_scale=out, dtype=dt,
                                    name="mamba", **self.mamba)(xn), None
        elif self.kind == "*":
            y, counts = GQAttention(h, out_scale=out, dtype=dt, name="attn",
                                    **self.attention)(xn), None
        elif self.kind == "E":
            y, counts = DroplessMoE(h, score="sigmoid", activation="relu2",
                                    down_init_scale=out, name="moe",
                                    **self.experts)(xn.reshape(b * s, h))
            y = y.reshape(b, s, h)
        else:
            raise ValueError(f"no layer of kind {self.kind!r}")
        return x + y.astype(x.dtype), counts


class NemotronHDecoder(nn.Module):
    """``__call__(tokens (b, s), labels (b, s))`` -> ``(loss, aux)``,
    ``aux = {"lm_loss", "expert_counts" (expert layers, experts_held)}``.
    Layer i is of the kind ``pattern[i]``, for the first ``num_layers``.
    ``mamba`` / ``attention`` / ``experts`` are the keyword arguments of
    ``Mamba2Mixer`` / ``GQAttention`` / ``DroplessMoE`` past the hidden
    size."""
    vocab_size: int
    hidden_size: int
    num_layers: int
    pattern: str
    mamba: dict
    attention: dict
    experts: dict
    rms_norm_eps: float = 1e-5
    dtype: jnp.dtype = F32

    @nn.compact
    def __call__(self, tokens, labels):
        h = self.hidden_size
        if self.num_layers > len(self.pattern):
            raise ValueError(f"{self.num_layers} layers, a pattern of "
                             f"{len(self.pattern)}")
        x = tp.VocabParallelEmbedding(
            self.vocab_size, h, init_method=_EMBED_INIT,
            name="embed")(tokens).astype(self.dtype)
        layer = nn.remat(NemotronHLayer)
        # rescale_prenorm_residual: every matrix that writes the residual
        # stream at 1/sqrt(layers), the layers the whole pattern counts
        out = 1.0 / math.sqrt(len(self.pattern))
        counts = []
        for i, kind in enumerate(self.pattern[:self.num_layers]):
            x, c = layer(kind, h, self.mamba, self.attention, self.experts,
                         self.rms_norm_eps, out, self.dtype,
                         name=f"layer_{i}")(x)
            if c is not None:
                counts.append(c)
        x = FusedRMSNorm(normalized_shape=h, eps=self.rms_norm_eps,
                         name="final_norm")(x).astype(self.dtype)
        w = self.param("head", _INIT, (h, self.vocab_size), F32)
        with jax.named_scope("apex_linear"):            # the untied head
            logits = jnp.dot(x, w.astype(self.dtype),
                             preferred_element_type=F32)
        lm_loss = jnp.mean(softmax_cross_entropy(
            logits.reshape(-1, self.vocab_size), labels.reshape(-1),
            0.0, True))
        return lm_loss, {"lm_loss": lm_loss,
                         "expert_counts": (jnp.stack(counts) if counts else
                                           jnp.zeros((0, 0), jnp.int32))}

    def loss(self, variables, tokens, labels):
        return self.apply(variables, tokens, labels)
