"""GPT / Megatron-style causal transformer — the flagship model family
(reference context: BASELINE config 4 "GPT-2 block: contrib.multihead_attn
+ FusedAdam"; the reference ships no models, apex_tpu does so the configs
run end-to-end).

Megatron anatomy on the TPU mesh:
  - QKV/out-proj and MLP as Column/RowParallelLinear over the "model"
    axis (apex/transformer/tensor_parallel/layers.py semantics)
  - optional sequence parallelism: activations sharded on the seq dim
    between TP regions (all_gather into the col-linear, reduce_scatter
    out of the row-linear)
  - causal attention through the fused flash kernel
    (apex_tpu.ops.attention), RoPE optional
  - FusedLayerNorm in f32, residuals in compute dtype
  - vocab-parallel embedding + tied LM head + vocab-parallel CE

Layout is Megatron's (s, b, h) between layers; attention transposes to
(b, heads, s, d) for the kernel.  Works at tp=1 anywhere, tp>1 inside
shard_map.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu import comm
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.ops.attention import (flash_attention,
                                    packed_segment_ids, ring_attention,
                                    ulysses_attention)
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.tensor_parallel import mappings


class GPTLayer(nn.Module):
    hidden_size: int
    num_heads: int
    ffn_hidden_size: Optional[int] = None
    sequence_parallel: bool = False
    use_rope: bool = False
    context_parallel: bool = False     # attention over the "ctx" axis
    cp_strategy: str = "ring"          # "ring" (ppermute) | "ulysses"
                                       # (all_to_all; local_heads % cp == 0)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, segment_ids=None, positions=None):
        """x: (s[, /tp if SP], b, h) -> same shape.

        segment_ids (b, s) / positions (b, s): packed-batch form
        (apex_tpu.data.pack_sequences) — attention masks across
        segments (disjoint padding ids per side, so padding rows
        output zeros) and RoPE rotates by within-sequence positions.
        BOTH or NEITHER: one-sided packing silently corrupts the
        other half (unmasked cross-segment attention, or every
        non-first segment rotated by its row offset).  Unsupported
        together with context_parallel (a packed row's segments would
        straddle ctx shards)."""
        if (segment_ids is None) != (positions is None):
            raise ValueError(
                "packed batches need BOTH segment_ids and positions "
                "(apex_tpu.data.pack_sequences emits both)")
        if segment_ids is not None and self.context_parallel:
            raise NotImplementedError(
                "packed segment_ids with context_parallel: split "
                "sequences across rows instead of packing, or drop cp")
        h = self.hidden_size
        ffn = self.ffn_hidden_size or 4 * h
        tp_size = comm.model_parallel_size()
        local_heads = self.num_heads // max(tp_size, 1)
        head_dim = h // self.num_heads

        ln1 = FusedLayerNorm(normalized_shape=h, name="input_layernorm",
                             sequence_parallel=self.sequence_parallel)
        qkv = tp.ColumnParallelLinear(
            h, 3 * h, gather_output=False,
            sequence_parallel_enabled=self.sequence_parallel,
            compute_dtype=self.dtype, name="attn_qkv")
        proj = tp.RowParallelLinear(
            h, h, input_is_parallel=True,
            sequence_parallel_enabled=self.sequence_parallel,
            compute_dtype=self.dtype, name="attn_proj")
        ln2 = FusedLayerNorm(normalized_shape=h,
                             name="post_attn_layernorm",
                             sequence_parallel=self.sequence_parallel)
        fc1 = tp.ColumnParallelLinear(
            h, ffn, gather_output=False,
            sequence_parallel_enabled=self.sequence_parallel,
            compute_dtype=self.dtype, name="mlp_fc1")
        fc2 = tp.RowParallelLinear(
            ffn, h, input_is_parallel=True,
            sequence_parallel_enabled=self.sequence_parallel,
            compute_dtype=self.dtype, name="mlp_fc2")

        # --- attention block ---
        y = ln1(x).astype(self.dtype)
        y = qkv(y)                                   # (s_full, b, 3h/tp)
        s_full, b = y.shape[0], y.shape[1]
        y = y.reshape(s_full, b, local_heads, 3 * head_dim)
        q, k, v = jnp.split(y, 3, axis=-1)

        def to_bhsd(t):
            return jnp.transpose(t, (1, 2, 0, 3))    # (b, lh, s, d)

        q, k, v = to_bhsd(q), to_bhsd(k), to_bhsd(v)
        if self.use_rope:
            inv = 1.0 / (10000.0 ** (
                jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
            if positions is not None:
                # packed: within-sequence positions, per row ->
                # freqs (s, b, 1, d) broadcasting over heads
                pos = jnp.transpose(positions, (1, 0)).astype(
                    jnp.float32)                        # (s, b)
                freqs = jnp.einsum("sb,d->sbd", pos, inv)
                freqs = jnp.concatenate([freqs, freqs], axis=-1)
                freqs = freqs[:, :, None, :]
            else:
                pos = jnp.arange(s_full, dtype=jnp.float32)
                if self.context_parallel:
                    # positions are GLOBAL: offset by this ctx
                    # shard's start (mirrors ring_attention's qpos)
                    pos = pos + (jax.lax.axis_index(comm.AXIS_CTX)
                                 * s_full).astype(jnp.float32)
                freqs = jnp.einsum("s,d->sd", pos, inv)
                freqs = jnp.concatenate([freqs, freqs], axis=-1)
                freqs = freqs[:, None, None, :]
            # rope expects (s, b, heads, d)
            def rope(t):
                t_sbhd = jnp.transpose(t, (2, 0, 1, 3))
                t_sbhd = fused_apply_rotary_pos_emb(t_sbhd, freqs)
                return jnp.transpose(t_sbhd, (1, 2, 0, 3))
            q, k = rope(q), rope(k)
        if self.context_parallel:
            if self.cp_strategy == "ulysses":
                attn = ulysses_attention(q, k, v, causal=True)
            elif self.cp_strategy == "ring":
                attn = ring_attention(q, k, v, causal=True)
            else:
                raise ValueError(
                    f"cp_strategy must be 'ring' or 'ulysses', got "
                    f"{self.cp_strategy!r}")
        elif segment_ids is not None:
            # disjoint pad ids per side (-1/-2): pad rows attend
            # nowhere and output exact zeros — convention single-
            # sourced in ops.attention.packed_segment_ids
            attn = flash_attention(q, k, v, causal=True,
                                   segment_ids=packed_segment_ids(
                                       segment_ids))
        else:
            attn = flash_attention(q, k, v, causal=True)
        attn = jnp.transpose(attn, (2, 0, 1, 3)).reshape(
            s_full, b, local_heads * head_dim)
        # offload tags (no-ops outside remat): the two largest
        # activations, usable with apex_tpu.offload.offload_checkpoint
        attn = checkpoint_name(attn, "attn_out")
        x = x + proj(attn).astype(x.dtype)

        # --- mlp block ---
        y = ln2(x).astype(self.dtype)
        y = checkpoint_name(jax.nn.gelu(fc1(y), approximate=True),
                            "ffn_hidden")
        x = x + fc2(y).astype(x.dtype)
        return x


class GPTStage(nn.Module):
    """A pipeline stage: k consecutive GPT layers (the stage_fn body for
    apex_tpu.transformer.pipeline_parallel.spmd)."""
    hidden_size: int
    num_heads: int
    num_layers: int
    ffn_hidden_size: Optional[int] = None
    sequence_parallel: bool = False
    use_rope: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, segment_ids=None, positions=None):
        for i in range(self.num_layers):
            x = GPTLayer(self.hidden_size, self.num_heads,
                         self.ffn_hidden_size,
                         sequence_parallel=self.sequence_parallel,
                         use_rope=self.use_rope, dtype=self.dtype,
                         name=f"layer_{i}")(x, segment_ids=segment_ids,
                                            positions=positions)
        return x


class GPTModel(nn.Module):
    """Full single-pipeline-stage GPT: embed -> layers -> ln -> tied head.

    __call__(tokens (b, s)) -> vocab-parallel logits (s, b, V/tp).
    ``loss(variables, tokens, labels)`` gives mean CE via the
    vocab-parallel loss.
    """
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_layers: int
    max_seq_len: int = 2048
    ffn_hidden_size: Optional[int] = None
    sequence_parallel: bool = False
    use_rope: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, segment_ids=None, positions=None):
        """tokens (b, s) -> vocab-parallel logits (s, b, V/tp).

        segment_ids / positions (both (b, s)): packed-batch training
        (apex_tpu.data.pack_sequences) — position lookups use the
        within-sequence positions and attention is segment-masked;
        pad rows (segment 0) produce garbage logits to be masked in
        the loss (e.g. padding_idx labels)."""
        b, s = tokens.shape
        if positions is not None and s > self.max_seq_len:
            # the unpacked path fails loudly via broadcast shape
            # mismatch; the gather path would silently CLAMP
            # out-of-range positions to the table's last row
            raise ValueError(
                f"packed rows of length {s} exceed max_seq_len="
                f"{self.max_seq_len}; pack at max_len <= max_seq_len")
        embed = tp.VocabParallelEmbedding(self.vocab_size,
                                          self.hidden_size, name="embed")
        x = embed(tokens)                              # (b, s, h)
        if not self.use_rope:
            pos = self.param("pos_embedding",
                             nn.initializers.normal(0.02),
                             (self.max_seq_len, self.hidden_size),
                             jnp.float32)
            x = x + (pos[positions] if positions is not None
                     else pos[:s][None, :, :])
        x = jnp.transpose(x, (1, 0, 2))                # (s, b, h)
        if self.sequence_parallel:
            x = mappings.scatter_to_sequence_parallel_region(x)
        x = x.astype(self.dtype)
        for i in range(self.num_layers):
            x = GPTLayer(self.hidden_size, self.num_heads,
                         self.ffn_hidden_size,
                         sequence_parallel=self.sequence_parallel,
                         use_rope=self.use_rope, dtype=self.dtype,
                         name=f"layer_{i}")(x, segment_ids=segment_ids,
                                            positions=positions)
        # The head's d/dx from the LOCAL vocab shard is a partial sum
        # over tp ranks; exactly ONE f-mapping must sync it (Megatron's
        # parallel_lm_logits layout).  Under SP that role is played by
        # the sequence-region exit gather (bwd = reduce-scatter), with
        # the final LN INSIDE the region (its param grads synced by its
        # sequence_parallel flag); without SP it is an explicit copy_to
        # (fwd identity / bwd psum).
        x = FusedLayerNorm(normalized_shape=self.hidden_size,
                           name="final_layernorm",
                           sequence_parallel=self.sequence_parallel)(x)
        if self.sequence_parallel:
            x = mappings.gather_from_sequence_parallel_region(x)
        elif comm.model_parallel_size() > 1:
            x = mappings.copy_to_tensor_model_parallel_region(x)
        w = self.get_variable("params", "embed")["weight"]
        with jax.named_scope("apex_linear"):    # the tied-embedding head
            logits = jnp.dot(x.astype(self.dtype),
                             jnp.transpose(w).astype(self.dtype),
                             preferred_element_type=jnp.float32)
        return logits                                  # (s, b, V/tp) f32

    def loss(self, variables, tokens, labels, segment_ids=None,
             positions=None):
        """Mean CE; with packed inputs, two position classes are
        excluded from the mean: padding (segment 0), whose logits are
        garbage by contract, and each segment's FINAL position — with
        the documented shift-by-one label construction (labels[i] =
        tokens[i+1], docs/transformer.md) a packed segment's last
        token would otherwise train against the NEXT segment's first
        token.  Callers that already set an ignore label there lose
        nothing; callers that shifted naively are silently correct."""
        logits = self.apply(variables, tokens,
                            segment_ids=segment_ids,
                            positions=positions)       # (s, b, V/tp)
        labels_sb = jnp.transpose(labels, (1, 0))      # (s, b)
        per_tok = tp.vocab_parallel_cross_entropy(logits, labels_sb)
        if segment_ids is None:
            return jnp.mean(per_tok)
        seg_sb = jnp.transpose(segment_ids, (1, 0))    # (s, b)
        next_seg = jnp.concatenate(
            [seg_sb[1:], jnp.zeros_like(seg_sb[:1])], axis=0)
        keep = (seg_sb > 0) & (next_seg == seg_sb)
        return (jnp.sum(per_tok * keep)
                / jnp.maximum(jnp.sum(keep), 1))
