"""Tensor-parallel layers (reference:
apex/transformer/tensor_parallel/layers.py).

ColumnParallelLinear / RowParallelLinear / VocabParallelEmbedding as flax
modules holding PER-SHARD parameters, written to run inside shard_map
over the "model" mesh axis (the Megatron per-rank view, which is also
what XLA compiles best: local matmuls + explicit collectives on ICI).
With tensor_model_parallel_size 1 they degrade to plain layers and run
anywhere.

Sequence parallelism (reference ``sequence_parallel_enabled``): column
fwd all-gathers the seq dim before the matmul, row fwd reduce-scatters
after — exactly the reference's substitution of all-reduce by
all_gather + reduce_scatter (SURVEY.md §2.2).

Weight init: each rank initializes its own shard with the master RNG
folded by tensor-parallel rank (see random.py), the TPU analog of the
reference's per-rank CUDA RNG tracker.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import comm
from apex_tpu.transformer.tensor_parallel import mappings
from apex_tpu.transformer.tensor_parallel.utils import (VocabUtility,
                                                        divide)

AXIS = comm.AXIS_MODEL


def _tp_world() -> int:
    return comm.model_parallel_size()


def _fold_tp_rank(key):
    if comm.axis_is_bound(AXIS):
        return jax.random.fold_in(key, jax.lax.axis_index(AXIS))
    return key


def _sharded_init(base_init: Callable):
    """Decorrelate per-rank shards by folding the tp rank into the rng."""
    def init(key, shape, dtype=jnp.float32):
        return base_init(_fold_tp_rank(key), shape, dtype)
    return init


def _local_matmul(x, w, fp8):
    """The per-rank local matmul shared by Column/RowParallelLinear:
    plain bf16/f32 dot, or — with an ``fp8``
    :class:`~apex_tpu.amp.fp8.Fp8Policy` — the e4m3-forward /
    e5m2-backward quantized path (``fused_dense.fp8_matmul``); the
    surrounding tensor-parallel collectives are unchanged (reductions
    always run on the DEQUANTIZED f32/compute-dtype output — never on
    raw fp8 values, the APX204 discipline)."""
    if fp8 is not None:
        from apex_tpu.fused_dense import fp8_matmul
        return fp8_matmul(x, w, policy=fp8)
    return jnp.dot(x, w, preferred_element_type=jnp.float32
                   ).astype(x.dtype)


class ColumnParallelLinear(nn.Module):
    """Y = X A + b with A sharded along its OUTPUT dim.

    Per-shard weight: (in, out/tp).  gather_output=True restores the full
    output (reference default); False leaves it model-parallel for a
    following RowParallelLinear.
    """
    input_size: int
    output_size: int
    bias: bool = True
    gather_output: bool = True
    init_method: Callable = nn.initializers.lecun_normal()
    stride: int = 1
    keep_master_weight_for_test: bool = False
    skip_bias_add: bool = False
    sequence_parallel_enabled: bool = False
    params_dtype: jnp.dtype = jnp.float32
    compute_dtype: Optional[jnp.dtype] = None
    fp8: Optional[object] = None

    @nn.compact
    def __call__(self, x):
        tp = _tp_world()
        out_local = divide(self.output_size, tp)
        w = self.param("weight", _sharded_init(self.init_method),
                       (self.input_size, out_local), self.params_dtype)
        b = (self.param("bias", nn.initializers.zeros, (out_local,),
                        self.params_dtype) if self.bias else None)
        if self.sequence_parallel_enabled:
            # x: (s/tp, b, in) -> gather full sequence
            x = mappings.gather_from_sequence_parallel_region(x, AXIS)
        elif tp > 1:
            x = mappings.copy_to_tensor_model_parallel_region(x, AXIS)
        with jax.named_scope("apex_linear"):
            dt = self.compute_dtype or x.dtype
            y = _local_matmul(x.astype(dt), w.astype(dt), self.fp8)
            if b is not None and not self.skip_bias_add:
                y = y + b.astype(dt)
        if self.gather_output and tp > 1:
            assert not self.sequence_parallel_enabled
            y = mappings.gather_from_tensor_model_parallel_region(y, AXIS)
        if self.skip_bias_add:
            return y, b
        return y


class RowParallelLinear(nn.Module):
    """Y = X A + b with A sharded along its INPUT dim.

    Per-shard weight: (in/tp, out).  input_is_parallel=True consumes the
    un-gathered output of a ColumnParallelLinear; the partial products
    are summed with psum (or reduce-scattered over the sequence dim under
    sequence parallelism).  Bias is added AFTER the reduction, once.
    """
    input_size: int
    output_size: int
    bias: bool = True
    input_is_parallel: bool = False
    init_method: Callable = nn.initializers.lecun_normal()
    stride: int = 1
    keep_master_weight_for_test: bool = False
    skip_bias_add: bool = False
    sequence_parallel_enabled: bool = False
    params_dtype: jnp.dtype = jnp.float32
    compute_dtype: Optional[jnp.dtype] = None
    fp8: Optional[object] = None

    @nn.compact
    def __call__(self, x):
        tp = _tp_world()
        in_local = divide(self.input_size, tp)
        if self.sequence_parallel_enabled and not self.input_is_parallel:
            raise RuntimeError(
                "To enable `sequence_parallel_enabled`, "
                "`input_is_parallel` must be `True`")
        w = self.param("weight", _sharded_init(self.init_method),
                       (in_local, self.output_size), self.params_dtype)
        b = (self.param("bias", nn.initializers.zeros, (self.output_size,),
                        self.params_dtype) if self.bias else None)
        if not self.input_is_parallel and tp > 1:
            x = mappings.scatter_to_tensor_model_parallel_region(x, AXIS)
        dt = self.compute_dtype or x.dtype
        with jax.named_scope("apex_linear"):
            y = _local_matmul(x.astype(dt), w.astype(dt), self.fp8)
        if tp > 1:
            if self.sequence_parallel_enabled:
                y = mappings.reduce_scatter_to_sequence_parallel_region(
                    y, AXIS)
            else:
                y = mappings.reduce_from_tensor_model_parallel_region(
                    y, AXIS)
        if b is not None and self.sequence_parallel_enabled and tp > 1:
            # the bias (added here or by a skip_bias_add caller) lands
            # on a SEQUENCE-SHARDED y: its grad is a local-shard sum,
            # so sync like the SP layernorm params (fwd identity / bwd
            # psum) — on BOTH return paths
            b = mappings.copy_to_tensor_model_parallel_region(b, AXIS)
        if self.skip_bias_add:
            return y, b
        if b is not None:
            with jax.named_scope("apex_linear"):
                y = y + b.astype(dt)
        return y


class VocabParallelEmbedding(nn.Module):
    """Embedding table sharded along the VOCAB dim.

    Each rank holds rows [rank*V/tp, (rank+1)*V/tp); out-of-range token
    lookups contribute zeros and the psum assembles the full embedding —
    the reference's masked-lookup + all-reduce."""
    num_embeddings: int
    embedding_dim: int
    init_method: Callable = nn.initializers.normal(stddev=0.02)
    params_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, ids):
        tp = _tp_world()
        v_local = divide(self.num_embeddings, tp)
        w = self.param("weight", _sharded_init(self.init_method),
                       (v_local, self.embedding_dim), self.params_dtype)
        if tp == 1:
            return jnp.take(w, ids, axis=0)
        rank = jax.lax.axis_index(AXIS)
        first, _ = VocabUtility.vocab_range_from_per_partition_vocab_size(
            v_local, rank, tp)
        local_ids = ids - first
        in_range = (local_ids >= 0) & (local_ids < v_local)
        local_ids = jnp.where(in_range, local_ids, 0)
        emb = jnp.take(w, local_ids, axis=0)
        emb = jnp.where(in_range[..., None], emb, 0.0)
        return mappings.reduce_from_tensor_model_parallel_region(emb, AXIS)
