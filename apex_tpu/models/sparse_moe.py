"""Decoder with learned sparse attention and a dropless expert layer.

    h' = h  + Attn( RMSNorm(h) )          # grouped-query attention over
    h''= h' + MoE ( RMSNorm(h') )         #   the keys an indexer selects
    loss = mean cross-entropy( W_head RMSNorm_f(h_L) ) + weight * sum_l L_I

Attention: ``x = RMSNorm(h)``; q (H heads), k, v (HK heads) without
biases, an RMSNorm over each head of q and k, rotary positions over the
whole head; every query attends the ``index_topk`` keys its layer's
indexer rates highest among s <= t (all of them while t < index_topk):

    q^I = x~ W_q^I (Hi heads of Di),  k^I = LayerNorm(x~ W_k^I) (one head),
    w = x~ W_w,  x~ = stop_gradient(x),  rotary on q^I and k^I
    I[t, s] = sum_j w[t, j] Hi^-1/2 Di^-1/2 relu(q^I[t, j] . k^I[s])

and the indexer learns from ``L_I`` alone, the divergence of
softmax over the selected keys of ``I`` from the main attention's own
head-averaged distribution over them (``apex_tpu/ops/sparse_index.py``;
the language loss sends the indexer nothing, ``L_I`` sends nothing
anywhere else).  The expert layer is ``transformer/moe.py:DroplessMoE``:
a float32 softmax router over ``num_experts``, top-k, gates normalised
over the k chosen, and THIS holder's ``experts_held`` experts starting
at ``expert_offset`` — the part of the layer's result they give goes on
to the next layer.

Every layer is rematerialised; beside its input a layer keeps two things
between its forward and its backward pass.  Its selection (int8, S x S
a sequence, checkpoint name ``apex_sparse_select``), so that the flash
forward is recomputed without the counting passes that turn scores into
a set.  And the gradient of ``L_I`` with respect to the indexer's own
leaves (``apex_sparse_index_grad``: 2.26 M numbers at the 30B-A3B
widths, 4.5 MB in bfloat16), made in the forward pass where the
objective's kernel has just written its gradient in the scores
(``_index_objective``): kept instead of those scores' gradient
(float32 S x S, 268 MB at 8192), it leaves the backward pass a scaling
by its cotangent, and the rematerialised pass runs nothing of the
indexer.

Scopes: ``apex_sparse_attn/{indexer,select,index_loss}``,
``apex_moe/{router,dispatch,experts,combine}``, ``apex_swiglu``,
``apex_linear``, ``apex_attention`` (docs/observability.md).  Layout
(b, s, h) between layers.  Written for tp=1.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.models.looped import rotary_freqs
from apex_tpu.normalization import FusedLayerNorm, FusedRMSNorm
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb
from apex_tpu.ops.sparse_index import (index_loss_and_grad, index_scores,
                                       select_topk)
from apex_tpu.ops.xentropy import softmax_cross_entropy
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.moe import DroplessMoE

_INIT = nn.initializers.normal(0.02)
# The token table starts at unit scale (torch's ``Embedding`` default).
# At 0.02 a pre-norm stream is, at initialisation, its attention's
# running mean — all but the same vector for every token — and every
# token of a layer routes to the same ``top_k`` experts.
_EMBED_INIT = nn.initializers.normal(1.0)
SELECTION = "apex_sparse_select"
INDEX_GRAD = "apex_sparse_index_grad"


def _scaled(tree, c):
    """``c`` times every leaf, multiplied in float32, in the leaf's
    dtype."""
    return jax.tree_util.tree_map(
        lambda x: (c * x.astype(jnp.float32)).astype(x.dtype), tree)


@jax.custom_vjp
def _with_gradient(value, theta, dtheta):
    """``value``, whose gradient with respect to ``theta`` is
    ``dtheta`` (already computed) and with respect to nothing else."""
    return value


def _with_gradient_fwd(value, theta, dtheta):
    return value, dtheta


def _with_gradient_bwd(dtheta, ct):
    return None, _scaled(dtheta, ct), None


_with_gradient.defvjp(_with_gradient_fwd, _with_gradient_bwd)


def _index_objective(scores_of, theta):
    """-> (scores, objective): ``scores = scores_of(theta)`` and
    ``objective(key_mask, q, k, lse)`` the indexer's loss ``L_I`` (see
    ``index_loss``), DIFFERENTIATED WHERE IT IS COMPUTED.  ``theta``,
    the indexer's leaves, is all ``L_I`` sends a gradient to, and that
    gradient is linear in the one scalar the backward pass brings.  So
    the kernel pass that gives the loss also gives its gradient with
    respect to the scores, ``scores_of``'s pullback takes that to
    ``dtheta`` there and then, and ``dtheta`` (checkpoint name
    ``INDEX_GRAD``) is what a rematerialised layer keeps: its backward
    pass scales it and runs nothing of the indexer.  Everything is
    evaluated at ``stop_gradient(theta)`` and handed over behind
    ``stop_gradient``: differentiated THROUGH, the pullback would bring
    the indexer back into the backward pass.  A program that asks for no
    gradient never reads ``dtheta``, and the compiler drops the
    pullback."""
    scores, pull = jax.vjp(scores_of, jax.lax.stop_gradient(theta))

    def objective(key_mask, q, k, lse):
        value, g = index_loss_and_grad(scores, key_mask, q, k, lse)
        dtheta, = pull(g)
        with jax.named_scope("apex_sparse_attn/index_loss"):
            rows = scores.shape[0] * scores.shape[1]    # L_I is their mean
            dtheta = _scaled(dtheta, 1.0 / rows)
        dtheta = checkpoint_name(jax.lax.stop_gradient(dtheta), INDEX_GRAD)
        return _with_gradient(jax.lax.stop_gradient(value), theta, dtheta)

    return scores, objective


class SparseMoEDecoderLayer(nn.Module):
    """One layer -> (h, (L_I, counts)): ``L_I`` this layer's indexer
    objective (float32 scalar), ``counts`` (experts_held,) the tokens
    routed to each held expert."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_ffn_hidden_size: int
    num_experts: int
    experts_held: int
    top_k: int
    index_heads: int
    index_head_dim: int
    index_topk: int
    expert_offset: int = 0
    norm_topk_prob: bool = True
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, freqs, index_freqs):
        h, d = self.hidden_size, self.head_dim
        nh, nkv = self.num_heads, self.num_kv_heads
        hi, di = self.index_heads, self.index_head_dim
        b, s, _ = x.shape

        def column(out, name):
            return tp.ColumnParallelLinear(
                h, out, bias=False, gather_output=False, init_method=_INIT,
                compute_dtype=self.dtype, name=name)

        # --- attention over the selected keys ---
        xn = FusedRMSNorm(normalized_shape=h, eps=self.eps,
                          name="attn_norm")(x).astype(self.dtype)
        qkv = column((nh + 2 * nkv) * d, "attn_qkv")(xn)      # [q | k | v]
        q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
        q = q.reshape(b, s, nh, d)
        k, v = (t.reshape(b, s, nkv, d) for t in (k, v))
        q = FusedRMSNorm(normalized_shape=d, eps=self.eps,
                         name="q_norm")(q).astype(self.dtype)
        k = FusedRMSNorm(normalized_shape=d, eps=self.eps,
                         name="k_norm")(k).astype(self.dtype)
        q = fused_apply_rotary_pos_emb(q, freqs)
        k = fused_apply_rotary_pos_emb(k, freqs)
        q, k, v = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))

        # the indexer reads the layer's input and sends it nothing
        xs = jax.lax.stop_gradient(xn)
        proj = column(hi * di + di + hi, "index_proj")       # [q | k | w]
        norm = FusedLayerNorm(normalized_shape=di, eps=self.eps,
                              name="index_k_norm")

        def indexer(project, normalize):
            qi, ki, w = jnp.split(project(xs), [hi * di, hi * di + di],
                                  axis=-1)
            ki = normalize(ki).astype(self.dtype)
            qi = fused_apply_rotary_pos_emb(qi.reshape(b, s, hi, di),
                                            index_freqs)
            ki = fused_apply_rotary_pos_emb(ki.reshape(b, s, 1, di),
                                            index_freqs)
            return index_scores(
                jnp.transpose(qi, (0, 2, 1, 3)), ki[:, :, 0],
                w.astype(jnp.float32) * (hi ** -0.5 * di ** -0.5))

        if self.is_initializing():
            indexer(proj, norm)                 # declares the two leaves
        (proj, proj_vars), (norm, norm_vars) = proj.unbind(), norm.unbind()
        theta = {"index_proj": proj_vars["params"],
                 "index_k_norm": norm_vars["params"]}

        def scores_of(theta):
            return indexer(
                lambda x: proj.apply({"params": theta["index_proj"]}, x),
                lambda x: norm.apply({"params": theta["index_k_norm"]}, x))

        scores, objective = _index_objective(scores_of, theta)
        selected = checkpoint_name(select_topk(scores, self.index_topk),
                                   SELECTION)
        attn, lse = flash_attention(q, k, v, causal=True,
                                    key_mask=selected, return_lse=True)
        l_index = objective(selected, q, k, lse)
        attn = jnp.transpose(attn, (0, 2, 1, 3)).reshape(b, s, nh * d)
        x = x + tp.RowParallelLinear(
            nh * d, h, bias=False, input_is_parallel=True,
            init_method=_INIT, compute_dtype=self.dtype,
            name="attn_proj")(attn).astype(x.dtype)

        # --- this holder's experts ---
        xn = FusedRMSNorm(normalized_shape=h, eps=self.eps,
                          name="mlp_norm")(x).astype(self.dtype)
        y, counts = DroplessMoE(
            h, self.moe_ffn_hidden_size, self.num_experts,
            self.experts_held, self.top_k, self.expert_offset,
            self.norm_topk_prob, name="moe")(xn.reshape(b * s, h))
        return x + y.reshape(b, s, h).astype(x.dtype), (l_index, counts)


class SparseMoEDecoder(nn.Module):
    """``__call__(tokens (b, s), labels (b, s))`` -> ``(loss, aux)``,
    ``aux = {"lm_loss", "index_loss", "expert_counts" (layers,
    experts_held)}``; ``loss = lm_loss + index_loss_weight *
    index_loss`` with ``index_loss`` the sum over layers."""
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_layers: int
    moe_ffn_hidden_size: int
    num_experts: int
    experts_held: int
    top_k: int
    index_heads: int
    index_head_dim: int
    index_topk: int
    expert_offset: int = 0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    index_loss_weight: float = 1.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, labels):
        h = self.hidden_size
        x = tp.VocabParallelEmbedding(
            self.vocab_size, h, init_method=_EMBED_INIT,
            name="embed")(tokens).astype(self.dtype)
        s = tokens.shape[1]
        freqs = rotary_freqs(s, self.head_dim, self.rope_theta)
        index_freqs = rotary_freqs(s, self.index_head_dim, self.rope_theta)
        layer = nn.remat(
            SparseMoEDecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(
                SELECTION, INDEX_GRAD))
        index_losses, counts = [], []
        for i in range(self.num_layers):
            x, (l_index, c) = layer(
                h, self.num_heads, self.num_kv_heads, self.head_dim,
                self.moe_ffn_hidden_size, self.num_experts,
                self.experts_held, self.top_k, self.index_heads,
                self.index_head_dim, self.index_topk, self.expert_offset,
                self.norm_topk_prob, self.rms_norm_eps, self.dtype,
                name=f"layer_{i}")(x, freqs, index_freqs)
            index_losses.append(l_index)
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
        l_index = jnp.sum(jnp.stack(index_losses))
        loss = lm_loss + self.index_loss_weight * l_index
        return loss, {"lm_loss": lm_loss, "index_loss": l_index,
                      "expert_counts": jnp.stack(counts)}

    def loss(self, variables, tokens, labels):
        return self.apply(variables, tokens, labels)
