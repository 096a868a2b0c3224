"""BERT encoder family (reference context: BASELINE config 3 "BERT-Large
pretraining: FusedLAMB + FusedLayerNorm + contrib.xentropy"; the
reference ships no models — this exists so the config runs end-to-end).

Same TPU-first anatomy as GPT (tensor/sequence-parallel linears, fused
flash attention, f32 FusedLayerNorm) but bidirectional with a padding
mask, post-LN residuals (BERT convention), learned position + segment
embeddings, and an MLM head whose loss is the fused softmax-xentropy
(apex_tpu.contrib.xentropy).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import comm
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.ops.attention import (attention_ref, flash_attention,
                                    packed_segment_ids)
from apex_tpu.transformer import tensor_parallel as tp


class BertLayer(nn.Module):
    hidden_size: int
    num_heads: int
    ffn_hidden_size: Optional[int] = None
    sequence_parallel: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, attn_mask=None, segment_ids=None):
        """x: (s, b, h); attn_mask: additive (b, 1, s, s) or None;
        segment_ids: (b, s) packed-batch form
        (apex_tpu.data.pack_sequences) routed through the flash
        kernel's segment masking — mutually exclusive with
        attn_mask."""
        if attn_mask is not None and segment_ids is not None:
            raise ValueError(
                "pass attn_mask OR segment_ids, not both (packed "
                "batches carry their mask in the segment ids)")
        h = self.hidden_size
        ffn = self.ffn_hidden_size or 4 * h
        tp_size = comm.model_parallel_size()
        local_heads = self.num_heads // max(tp_size, 1)
        head_dim = h // self.num_heads

        qkv = tp.ColumnParallelLinear(
            h, 3 * h, gather_output=False,
            sequence_parallel_enabled=self.sequence_parallel,
            compute_dtype=self.dtype, name="attn_qkv")
        proj = tp.RowParallelLinear(
            h, h, input_is_parallel=True,
            sequence_parallel_enabled=self.sequence_parallel,
            compute_dtype=self.dtype, name="attn_proj")
        ln1 = FusedLayerNorm(normalized_shape=h, name="attn_layernorm",
                             sequence_parallel=self.sequence_parallel)
        fc1 = tp.ColumnParallelLinear(
            h, ffn, gather_output=False,
            sequence_parallel_enabled=self.sequence_parallel,
            compute_dtype=self.dtype, name="mlp_fc1")
        fc2 = tp.RowParallelLinear(
            ffn, h, input_is_parallel=True,
            sequence_parallel_enabled=self.sequence_parallel,
            compute_dtype=self.dtype, name="mlp_fc2")
        ln2 = FusedLayerNorm(normalized_shape=h, name="mlp_layernorm",
                             sequence_parallel=self.sequence_parallel)

        y = qkv(x.astype(self.dtype))
        s_full, b = y.shape[0], y.shape[1]
        y = y.reshape(s_full, b, local_heads, 3 * head_dim)
        q, k, v = jnp.split(y, 3, axis=-1)
        q, k, v = (jnp.transpose(t, (1, 2, 0, 3)) for t in (q, k, v))
        if segment_ids is not None:
            attn = flash_attention(q, k, v, False,
                                   segment_ids=packed_segment_ids(
                                       segment_ids))
        elif attn_mask is None:
            attn = flash_attention(q, k, v, False)
        else:
            attn = attention_ref(q, k, v, mask=attn_mask)
        attn = jnp.transpose(attn, (2, 0, 1, 3)).reshape(
            s_full, b, local_heads * head_dim)
        x = ln1(x + proj(attn).astype(x.dtype))
        y = jax.nn.gelu(fc1(x.astype(self.dtype)), approximate=True)
        x = ln2(x + fc2(y).astype(x.dtype))
        return x


class BertModel(nn.Module):
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_layers: int
    max_seq_len: int = 512
    type_vocab_size: int = 2
    dtype: jnp.dtype = jnp.float32
    sequence_parallel: bool = False

    @nn.compact
    def __call__(self, tokens, token_type_ids=None, attention_mask=None,
                 segment_ids=None, positions=None):
        """tokens: (b, s) -> sequence output (s, b, h).

        segment_ids / positions (both (b, s)): packed-batch form
        (apex_tpu.data.pack_sequences) — BOTH or NEITHER; position
        lookups use within-sequence positions and attention is
        segment-masked (pad rows garbage, mask downstream via
        segment_ids == 0).  Mutually exclusive with attention_mask.
        NOTE: BERT "token type" (sentence A/B) ids remain
        token_type_ids — unrelated to packing segment ids."""
        if (segment_ids is None) != (positions is None):
            raise ValueError(
                "packed batches need BOTH segment_ids and positions "
                "(apex_tpu.data.pack_sequences emits both)")
        if segment_ids is not None and attention_mask is not None:
            raise ValueError(
                "pass attention_mask OR segment_ids, not both")
        b, s = tokens.shape
        if positions is not None and s > self.max_seq_len:
            raise ValueError(
                f"packed rows of length {s} exceed max_seq_len="
                f"{self.max_seq_len}; pack at max_len <= max_seq_len")
        embed = tp.VocabParallelEmbedding(self.vocab_size,
                                          self.hidden_size, name="embed")
        x = embed(tokens)
        pos = self.param("pos_embedding", nn.initializers.normal(0.02),
                         (self.max_seq_len, self.hidden_size), jnp.float32)
        x = x + (pos[positions] if positions is not None
                 else pos[:s][None, :, :])
        if token_type_ids is not None:
            seg = self.param("segment_embedding",
                             nn.initializers.normal(0.02),
                             (self.type_vocab_size, self.hidden_size),
                             jnp.float32)
            x = x + jnp.take(seg, token_type_ids, axis=0)
        x = FusedLayerNorm(normalized_shape=self.hidden_size,
                           name="embed_layernorm")(x)
        x = jnp.transpose(x, (1, 0, 2)).astype(self.dtype)   # (s, b, h)
        if self.sequence_parallel:
            x = tp.scatter_to_sequence_parallel_region(x)
        mask = None
        if attention_mask is not None:
            # (b, s) 1=keep -> additive (b, 1, 1, s)
            mask = (1.0 - attention_mask[:, None, None, :].astype(
                jnp.float32)) * -10000.0
        for i in range(self.num_layers):
            x = BertLayer(self.hidden_size, self.num_heads,
                          sequence_parallel=self.sequence_parallel,
                          dtype=self.dtype, name=f"layer_{i}")(
                x, mask, segment_ids=segment_ids)
        if self.sequence_parallel:
            x = tp.gather_from_sequence_parallel_region(x)
        return x

    def mlm_logits(self, variables, tokens, **kw):
        x = self.apply(variables, tokens, **kw)        # (s, b, h)
        # see GPTModel's head: exactly ONE f-mapping syncs d/dx of
        # the vocab-sharded head — under SP the encoder's exit gather
        # already is it (bwd reduce-scatter); without SP, copy_to
        if (comm.model_parallel_size() > 1
                and not self.sequence_parallel):
            x = tp.copy_to_tensor_model_parallel_region(x)
        w = variables["params"]["embed"]["weight"]
        with jax.named_scope("apex_linear"):    # the tied-embedding head
            return jnp.dot(x.astype(self.dtype),
                           jnp.transpose(w).astype(self.dtype),
                           preferred_element_type=jnp.float32)


def bert_large(**kw) -> BertModel:
    return BertModel(vocab_size=kw.pop("vocab_size", 30528),
                     hidden_size=1024, num_heads=16, num_layers=24, **kw)


def bert_base(**kw) -> BertModel:
    return BertModel(vocab_size=kw.pop("vocab_size", 30528),
                     hidden_size=768, num_heads=12, num_layers=12, **kw)
