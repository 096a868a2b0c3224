"""Looped decoder: ONE stack of layers applied ``num_passes`` times to
its own output with the same weights, an exit after every pass (final
norm, untied head, per-token cross-entropy, a learned exit gate), the
exits' losses combined by the exit distribution with an entropy bonus
(the looped-language-model family of arXiv:2510.25741).

    h = E[tokens]
    for t in 1..R:                                   # the same weights
        for l in 1..L:
            a = h + RMSNorm( Attn( RMSNorm(h) ) )    # sandwich norms
            h = a + RMSNorm( W_down( silu(W_gate u) * (W_up u) ) ),
                                                     #   u = RMSNorm(a)
        x_t = RMSNorm_f(h);  h = x_t                 # closes every pass
        l_t = cross_entropy(W_head x_t, labels)      # per token, f32
        z_t = w_g . x_t + b_g;  lam_t = sigmoid(z_t)
    p_t = lam_t prod_{j<t}(1 - lam_j) (t < R);  p_R = prod_{j<R}(1 - lam_j)
    loss = mean_tokens( sum_t p_t l_t - beta * H(p) )

Built from the library's parts: Column/RowParallelLinear (every matmul
under ``apex_linear``), ``fused_rms_norm``, rotary positions through
``fused_apply_rotary_pos_emb`` (rotate-half over the whole head),
``flash_attention(causal=True)``, ``softmax_cross_entropy``.  The
passes are ONE ``lax.scan`` whose body holds the stack once, the
weights broadcast into it (closed over, so the backward pass sums the
R contributions to every weight's gradient in the weights' dtype);
every layer application and every exit is rematerialised
(``jax.checkpoint``), so a step keeps R*L layer inputs and one exit's
logits at a time.  Scopes: ``apex_loop/body``, ``apex_loop/exit``,
``apex_loop/gate``, ``apex_swiglu`` (docs/observability.md).

Layout is (b, s, h) between layers; attention transposes to
(b, heads, s, d) for the kernel.  Written for tp=1: the layers are the
tensor-parallel linears, the head and its loss are not vocab-parallel.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import comm
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb
from apex_tpu.ops.xentropy import softmax_cross_entropy
from apex_tpu.transformer import tensor_parallel as tp

_INIT = nn.initializers.normal(0.02)


def rotary_freqs(seq_len: int, head_dim: int, theta: float):
    """(1, s, 1, d) angles of rotate-half rotary positions over the
    whole head, float32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    freqs = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None]
    return jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]


def exit_distribution(z):
    """log p_t of the exit distribution from the gates' logits ``z``
    (R, ...): p_t = lam_t prod_{j<t}(1 - lam_j), the last pass takes
    what is left (its own gate is not read).  Sums to 1 over t."""
    log_stay = jax.nn.log_sigmoid(-z)                 # log(1 - lam)
    before = jnp.cumsum(log_stay, axis=0) - log_stay  # sum over j < t
    log_p = jax.nn.log_sigmoid(z) + before
    return log_p.at[-1].set(before[-1])


@jax.named_scope("apex_loop/gate")
def combine_exits(losses, z, entropy_weight: float):
    """Mean over tokens of  sum_t p_t l_t - beta * H(p);  ``losses``
    and ``z`` are (R, tokens...) float32."""
    log_p = exit_distribution(z)
    p = jnp.exp(log_p)
    expected = jnp.sum(p * losses, axis=0)
    entropy = -jnp.sum(p * log_p, axis=0)
    return jnp.mean(expected - entropy_weight * entropy)


class LoopedDecoderLayer(nn.Module):
    """One layer: a norm before AND after each sub-layer, rotary causal
    attention, a gated ``silu`` feed-forward; no bias anywhere."""
    hidden_size: int
    num_heads: int
    ffn_hidden_size: int
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, freqs):
        """x: (b, s, h), freqs: ``rotary_freqs`` -> (b, s, h)."""
        h, ffn = self.hidden_size, self.ffn_hidden_size
        local_heads = self.num_heads // max(comm.model_parallel_size(), 1)
        head_dim = h // self.num_heads

        def norm(name):
            return FusedRMSNorm(normalized_shape=h, eps=self.eps, name=name)

        def column(out, name):
            return tp.ColumnParallelLinear(
                h, out, bias=False, gather_output=False, init_method=_INIT,
                compute_dtype=self.dtype, name=name)

        def row(inp, name):
            return tp.RowParallelLinear(
                inp, h, bias=False, input_is_parallel=True,
                init_method=_INIT, compute_dtype=self.dtype, name=name)

        # --- attention block ---
        y = column(3 * h, "attn_qkv")(norm("attn_norm")(x).astype(self.dtype))
        b, s = y.shape[0], y.shape[1]
        y = y.reshape(b, s, local_heads, 3 * head_dim)
        q, k, v = jnp.split(y, 3, axis=-1)
        q = fused_apply_rotary_pos_emb(q, freqs)
        k = fused_apply_rotary_pos_emb(k, freqs)
        q, k, v = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))
        attn = flash_attention(q, k, v, causal=True)
        attn = jnp.transpose(attn, (0, 2, 1, 3)).reshape(
            b, s, local_heads * head_dim)
        y = row(h, "attn_proj")(attn)
        x = x + norm("attn_out_norm")(y).astype(x.dtype)

        # --- gated feed-forward block ---
        y = column(2 * ffn, "mlp_gate_up")(
            norm("mlp_norm")(x).astype(self.dtype))
        with jax.named_scope("apex_swiglu"):
            gate, up = jnp.split(y.astype(jnp.float32), 2, axis=-1)
            y = (jax.nn.silu(gate) * up).astype(self.dtype)
        y = row(ffn, "mlp_down")(y)
        return x + norm("mlp_out_norm")(y).astype(x.dtype)


class LoopedExit(nn.Module):
    """What closes a pass: the final norm (its output feeds the next
    pass), the untied head with the per-token cross-entropy in float32,
    and the exit gate's logit."""
    vocab_size: int
    hidden_size: int
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, labels):
        """h (b, s, h), labels (b, s) -> (x, losses (b, s), z (b, s))."""
        hidden = self.hidden_size
        with jax.named_scope("apex_loop/exit"):
            x = FusedRMSNorm(normalized_shape=hidden, eps=self.eps,
                             name="final_norm")(h).astype(self.dtype)
            w = self.param("head", _INIT, (hidden, self.vocab_size),
                           jnp.float32)
            with jax.named_scope("apex_linear"):        # the untied head
                logits = jnp.dot(x, w.astype(self.dtype),
                                 preferred_element_type=jnp.float32)
            losses = softmax_cross_entropy(
                logits.reshape(-1, self.vocab_size), labels.reshape(-1),
                0.0, True).reshape(labels.shape)
        with jax.named_scope("apex_loop/gate"):
            wg = self.param("gate_weight", _INIT, (hidden,), jnp.float32)
            bg = self.param("gate_bias", nn.initializers.zeros, (1,),
                            jnp.float32)
            z = jnp.einsum("bsh,h->bs", x.astype(jnp.float32),
                           wg.astype(jnp.float32)) + bg.astype(jnp.float32)
        return x, losses, z


class LoopedPass(nn.Module):
    """One pass: the stack, then the exit.  ``(h, (freqs, labels)) ->
    (x, (losses, z))``, the body of the scan over passes; applied alone
    it is one pass of an untied model."""
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_layers: int
    ffn_hidden_size: int
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, inputs):
        freqs, labels = inputs
        # inside the scan nothing can be merged across the checkpoint's
        # boundary, so the CSE barrier would only cost
        layer = nn.remat(LoopedDecoderLayer, prevent_cse=False)
        with jax.named_scope("apex_loop/body"):
            for i in range(self.num_layers):
                h = layer(self.hidden_size, self.num_heads,
                          self.ffn_hidden_size, self.eps, self.dtype,
                          name=f"layer_{i}")(h, freqs)
        x, losses, z = nn.remat(LoopedExit, prevent_cse=False)(
            self.vocab_size, self.hidden_size, self.eps, self.dtype,
            name="exit")(h, labels)
        return x, (losses, z)


class LoopedDecoder(nn.Module):
    """``__call__(tokens (b, s), labels (b, s))`` -> the training loss;
    ``exits`` gives each pass's per-token losses and gate logits."""
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_layers: int
    ffn_hidden_size: int
    num_passes: int = 4
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    entropy_weight: float = 0.1
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.embed = tp.VocabParallelEmbedding(
            self.vocab_size, self.hidden_size, init_method=_INIT)
        self.stack = nn.scan(
            LoopedPass, variable_broadcast="params",
            split_rngs={"params": False}, in_axes=nn.broadcast,
            length=self.num_passes)(
                self.vocab_size, self.hidden_size, self.num_heads,
                self.num_layers, self.ffn_hidden_size, self.rms_norm_eps,
                self.dtype)

    def exits(self, tokens, labels):
        """-> (losses, z), each (R, b, s) float32."""
        h = self.embed(tokens).astype(self.dtype)
        freqs = rotary_freqs(tokens.shape[1],
                             self.hidden_size // self.num_heads,
                             self.rope_theta)
        return self.stack(h, (freqs, labels))[1]

    def __call__(self, tokens, labels):
        losses, z = self.exits(tokens, labels)
        return combine_exits(losses, z, self.entropy_weight)

    def loss(self, variables, tokens, labels):
        return self.apply(variables, tokens, labels)
