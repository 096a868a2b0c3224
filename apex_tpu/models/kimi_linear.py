"""Hybrid linear-attention expert decoder (Kimi Linear, arXiv:2510.26692):
layers of two kinds, Kimi Delta Attention (KDA) and latent attention
(MLA) without positions, each followed by a dense gated feed-forward
(the first ``first_k_dense_replace`` layers) or an expert layer.

    h' = h  + Mixer( RMSNorm(h) )       # KDA, or MLA in full_attn_layers
    h''= h' + FFN  ( RMSNorm(h') )      # dense, or sigmoid-routed experts
    loss = mean cross-entropy( W_head RMSNorm_f(h_L) )

KDA (``ops/kda.py``), per head of ``kda_head_dim``:

    q, k, v = SiLU(ShortConv_K(x W_{q,k,v}));  q, k <- q/|q|, k/|k|
    beta = sigmoid(x W_b)                       (one a head)
    g = -exp(A_log) * softplus(x W_f^down W_f^up + dt_bias)   (a channel)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = d^-1/2 S_t^T q_t
    y = W_o [ RMSNorm_head(o) * w * sigmoid(x W_g^down W_g^up) ]

MLA without positions (``mla_use_nope``): q = x W_q (heads of
nope + rope), [c ; k^R] = x W_kva, [k^C ; v] = RMSNorm(c) W_kvb,
k = [k^C ; k^R] (k^R shared by the heads), causal softmax attention at
(nope + rope)^-1/2 with values of their own width (``flash_attention``
with dv != d).

Experts (``transformer/moe.py:DroplessMoE``): s = sigmoid(x W_r) over
ALL ``num_experts``, the top-k of s + b (``selection_bias``, which
picks and does not weigh), gates s of the chosen renormalised over the
k and times ``routed_scale``; THIS holder's ``experts_held`` experts
from ``expert_offset``, and one shared expert every token goes through.

Every layer is rematerialised (its input is all it keeps) and returns
its held experts' token counts.  Scopes: ``apex_kda/{conv,gate,scan,
norm}`` (the scan's kernels ``apex_kda_fwd`` / ``apex_kda_bwd``),
``apex_mla``, ``apex_moe/{router,dispatch,experts,combine,shared}``,
``apex_swiglu``, ``apex_linear``, ``apex_attention``
(docs/observability.md).  Layout (b, s, h) between layers.  Written for
tp=1.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.mixer_init import a_log_init, conv_init, dt_bias_init
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.kda import chunk_kda, l2_normalize
from apex_tpu.ops.short_conv import short_conv
from apex_tpu.ops.xentropy import softmax_cross_entropy
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.moe import DroplessMoE

_INIT = nn.initializers.normal(0.02)
# unit scale for the token table, as the expert decoder's and for its
# reason (models/sparse_moe.py): at 0.02 every token routes alike
_EMBED_INIT = nn.initializers.normal(1.0)


def _column(inp, out, name, dtype):
    return tp.ColumnParallelLinear(inp, out, bias=False, gather_output=False,
                                   init_method=_INIT, compute_dtype=dtype,
                                   name=name)


def _row(inp, out, name, dtype):
    return tp.RowParallelLinear(inp, out, bias=False, input_is_parallel=True,
                                init_method=_INIT, compute_dtype=dtype,
                                name=name)


class KDAMixer(nn.Module):
    """Kimi Delta Attention over a normed input (b, s, h) -> (b, s, h)."""
    hidden_size: int
    num_heads: int
    head_dim: int
    conv_size: int = 4
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h, nh, d, dt = self.hidden_size, self.num_heads, self.head_dim, \
            self.dtype
        b, s, _ = x.shape
        width = nh * d

        def branch(name):           # projection, short convolution, SiLU
            u = _column(h, width, f"{name}_proj", dt)(x)
            w = self.param(f"{name}_conv", conv_init, (self.conv_size, width),
                           jnp.float32)
            return short_conv(u, w, scope="apex_kda/conv").reshape(
                b, s, nh, d)

        q, k, v = branch("q"), branch("k"), branch("v")
        q, k = l2_normalize(q), l2_normalize(k)
        f = _column(d, width, "f_b_proj", dt)(_column(h, d, "f_a_proj", dt)(x))
        beta = _column(h, nh, "b_proj", dt)(x)
        a_log = self.param("A_log", a_log_init, (nh,), jnp.float32)
        dt_bias = self.param("dt_bias", dt_bias_init, (width,), jnp.float32)
        with jax.named_scope("apex_kda/gate"):
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                f.astype(jnp.float32).reshape(b, s, nh, d)
                + dt_bias.reshape(nh, d))
            beta = jax.nn.sigmoid(beta.astype(jnp.float32))
        o = chunk_kda(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v, g, beta)))
        o = jnp.swapaxes(o, 1, 2)                           # (b, s, nh, d)
        gate = _column(d, width, "g_b_proj", dt)(
            _column(h, d, "g_a_proj", dt)(x))
        w = self.param("o_norm", nn.initializers.ones, (d,), jnp.float32)
        with jax.named_scope("apex_kda/norm"):
            o = o.astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + self.eps) * w
            o = o.reshape(b, s, width) * jax.nn.sigmoid(
                gate.astype(jnp.float32))
        return _row(width, h, "o_proj", dt)(o.astype(dt))


class MLAMixer(nn.Module):
    """Latent attention without positions over a normed input."""
    hidden_size: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h, nh, dt = self.hidden_size, self.num_heads, self.dtype
        nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        r = self.kv_lora_rank
        b, s, _ = x.shape
        q = _column(h, nh * (nope + rope), "q_proj", dt)(x)
        c, kr = jnp.split(_column(h, r + rope, "kv_a_proj", dt)(x), [r],
                          axis=-1)
        c = FusedRMSNorm(normalized_shape=r, eps=self.eps,
                         name="kv_a_norm")(c).astype(dt)
        kv = _column(r, nh * (nope + dv), "kv_b_proj", dt)(c)
        with jax.named_scope("apex_mla"):
            kc, v = jnp.split(kv.reshape(b, s, nh, nope + dv), [nope],
                              axis=-1)
            k = jnp.concatenate(
                [kc, jnp.broadcast_to(kr[:, :, None, :], (b, s, nh, rope))],
                axis=-1)
            q = q.reshape(b, s, nh, nope + rope)
            o = flash_attention(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)),
                                causal=True)
            o = jnp.swapaxes(o, 1, 2).reshape(b, s, nh * dv)
        return _row(nh * dv, h, "o_proj", dt)(o)


class KimiLinearLayer(nn.Module):
    """One layer -> (h, counts): ``counts`` (experts_held,) the tokens
    routed to each held expert, None for a dense layer."""
    hidden_size: int
    mixer: str                          # "kda" or "mla"
    dense: bool
    num_heads: int
    kda_head_dim: int
    conv_size: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    ffn_hidden_size: int
    moe_ffn_hidden_size: int
    num_experts: int
    experts_held: int
    top_k: int
    expert_offset: int
    routed_scale: float
    shared_ffn_hidden_size: int
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h, dt = self.hidden_size, self.dtype
        b, s, _ = x.shape
        xn = FusedRMSNorm(normalized_shape=h, eps=self.eps,
                          name="attn_norm")(x).astype(dt)
        if self.mixer == "kda":
            y = KDAMixer(h, self.num_heads, self.kda_head_dim, self.conv_size,
                         self.eps, dt, name="kda")(xn)
        else:
            y = MLAMixer(h, self.num_heads, self.kv_lora_rank,
                         self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim, self.eps, dt, name="mla")(xn)
        x = x + y.astype(x.dtype)
        xn = FusedRMSNorm(normalized_shape=h, eps=self.eps,
                          name="mlp_norm")(x).astype(dt)
        if self.dense:
            f = self.ffn_hidden_size
            y = _column(h, 2 * f, "mlp_gate_up", dt)(xn)
            with jax.named_scope("apex_swiglu"):
                gate, up = jnp.split(y.astype(jnp.float32), 2, axis=-1)
                y = (jax.nn.silu(gate) * up).astype(dt)
            return x + _row(f, h, "mlp_down", dt)(y).astype(x.dtype), None
        y, counts = DroplessMoE(
            h, self.moe_ffn_hidden_size, self.num_experts, self.experts_held,
            self.top_k, self.expert_offset, True, "sigmoid",
            self.routed_scale, self.shared_ffn_hidden_size,
            name="moe")(xn.reshape(b * s, h))
        return x + y.reshape(b, s, h).astype(x.dtype), counts


class KimiLinearDecoder(nn.Module):
    """``__call__(tokens (b, s), labels (b, s))`` -> ``(loss, aux)``,
    ``aux = {"lm_loss", "expert_counts" (expert layers, experts_held)}``.
    Layer i (from 0) is MLA when i + 1 is in ``full_attn_layers`` (the
    configuration's 1-based numbering), KDA otherwise, and dense when
    i < ``first_k_dense_replace``."""
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    full_attn_layers: Sequence[int]
    first_k_dense_replace: int
    ffn_hidden_size: int
    moe_ffn_hidden_size: int
    num_experts: int
    experts_held: int
    top_k: int
    expert_offset: int = 0
    routed_scale: float = 1.0
    shared_ffn_hidden_size: int = 0
    kda_head_dim: int = 128
    conv_size: int = 4
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, labels):
        h = self.hidden_size
        x = tp.VocabParallelEmbedding(
            self.vocab_size, h, init_method=_EMBED_INIT,
            name="embed")(tokens).astype(self.dtype)
        layer = nn.remat(KimiLinearLayer)
        counts = []
        for i in range(self.num_layers):
            x, c = layer(
                h, "mla" if i + 1 in self.full_attn_layers else "kda",
                i < self.first_k_dense_replace, self.num_heads,
                self.kda_head_dim, self.conv_size, self.kv_lora_rank,
                self.qk_nope_head_dim, self.qk_rope_head_dim,
                self.v_head_dim, self.ffn_hidden_size,
                self.moe_ffn_hidden_size, self.num_experts,
                self.experts_held, self.top_k, self.expert_offset,
                self.routed_scale, self.shared_ffn_hidden_size,
                self.rms_norm_eps, self.dtype, name=f"layer_{i}")(x)
            if c is not None:
                counts.append(c)
        x = FusedRMSNorm(normalized_shape=h, eps=self.rms_norm_eps,
                         name="final_norm")(x).astype(self.dtype)
        w = self.param("head", _INIT, (h, self.vocab_size), jnp.float32)
        with jax.named_scope("apex_linear"):            # the untied head
            logits = jnp.dot(x, w.astype(self.dtype),
                             preferred_element_type=jnp.float32)
        lm_loss = jnp.mean(softmax_cross_entropy(
            logits.reshape(-1, self.vocab_size), labels.reshape(-1),
            0.0, True))
        return lm_loss, {"lm_loss": lm_loss,
                         "expert_counts": jnp.stack(counts)}

    def loss(self, variables, tokens, labels):
        return self.apply(variables, tokens, labels)
