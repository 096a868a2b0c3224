"""apex_tpu — a TPU-native rebuild of the capabilities of NVIDIA Apex
(reference fork: wutianyiRosun/apex).

The reference is a CUDA/C++/torch "performance add-on" library: mixed
precision (apex.amp), fused kernels behind torch-shaped classes
(FusedAdam, FusedLayerNorm, ...), and distributed training utilities
(apex.parallel, apex.transformer).  This package re-designs the same
capability surface TPU-first:

  - compute path  : JAX / XLA / Pallas (Mosaic) kernels, bf16-centric
  - parallelism   : one global ``jax.sharding.Mesh`` (data/pipe/ctx/model
                    axes), XLA collectives over ICI/DCN via shard_map/pjit
  - precision     : O0-O3 policy tables (apex/amp/frontend.py parity) as
                    tracing-time dtype policies, not monkey-patching
  - optimizers    : pytree transforms + apex-shaped class facades
  - runtime glue  : C++ where host-side native code is warranted

Module map mirrors the reference package layout (SURVEY.md §2) so a user
of the reference can find everything in the same place:

  apex.amp                  -> apex_tpu.amp
  apex.optimizers           -> apex_tpu.optimizers
  apex.normalization        -> apex_tpu.normalization
  apex.multi_tensor_apply   -> apex_tpu.multi_tensor_apply
  apex.parallel             -> apex_tpu.parallel
  apex.transformer          -> apex_tpu.transformer
  apex.contrib              -> apex_tpu.contrib
  apex.mlp / fused_dense    -> apex_tpu.mlp / apex_tpu.fused_dense
  apex.fp16_utils           -> apex_tpu.fp16_utils
  apex.RNN                  -> apex_tpu.RNN
  apex.reparameterization   -> apex_tpu.reparameterization
  csrc/ (CUDA kernels)      -> apex_tpu.ops (Pallas kernels + XLA paths)

Beyond-reference TPU tiers (no apex counterpart): apex_tpu.data (device
prefetcher), apex_tpu.offload (host-memory offload), apex_tpu.checkpoint
(packed/async checkpoints) + apex_tpu.resilience (crash recovery),
apex_tpu.quantization (int8 inference), apex_tpu.platform (backend pin
for --cpu flags, the one compile-cache setter), apex_tpu.telemetry
(host-sync-free training telemetry: device-side metric ring, span
timing, retrace counters — docs/observability.md).
"""

from apex_tpu._version import __version__
from apex_tpu import comm
from apex_tpu.telemetry import retrace as _retrace

# the process's set-up account (telemetry/retrace.py): every program
# traced, lowered or loaded from here to the first optimizer steps is
# in it; it closes itself after them
_retrace.process().install()

# Feature-detection registry: the reference gates optional features on
# "is my CUDA extension importable?" (setup.py --xentropy etc., SURVEY.md §5
# config/flag system).  Here each reference extension name maps to the
# apex_tpu module that replaces it; availability is probed by import so the
# table can never advertise something that does not exist.
_FEATURE_MODULES = {
    "amp_C": "apex_tpu.ops.multi_tensor",
    "apex_C": "apex_tpu.multi_tensor_apply",
    "fused_layer_norm_cuda": "apex_tpu.ops.layer_norm",
    "fast_layer_norm": "apex_tpu.ops.layer_norm",
    "syncbn": "apex_tpu.ops.welford",
    "mlp_cuda": "apex_tpu.mlp",
    "fused_dense_cuda": "apex_tpu.fused_dense",
    "scaled_masked_softmax_cuda": "apex_tpu.ops.softmax",
    "scaled_upper_triang_masked_softmax_cuda": "apex_tpu.ops.softmax",
    "generic_scaled_masked_softmax_cuda": "apex_tpu.ops.softmax",
    "fused_rotary_positional_embedding": "apex_tpu.ops.rope",
    "fused_weight_gradient_mlp_cuda": "apex_tpu.ops.wgrad",
    "xentropy_cuda": "apex_tpu.ops.xentropy",
    "fast_multihead_attn": "apex_tpu.ops.attention",
    "fmhalib": "apex_tpu.ops.attention",
    "transducer_joint_cuda": "apex_tpu.ops.transducer",
    "transducer_loss_cuda": "apex_tpu.ops.transducer",
    "distributed_adam_cuda": "apex_tpu.contrib.optimizers",
    "distributed_lamb_cuda": "apex_tpu.contrib.optimizers",
    "bnp": "apex_tpu.contrib.groupbn",
    # GPU-physics-bound features with no TPU analog (documented stubs):
    "peer_memory_cuda": None,
    "nccl_p2p_cuda": None,
    "nccl_allocator": None,
    "gpu_direct_storage": None,
}

_feature_cache = {}


def has_feature(name: str) -> bool:
    """Parity shim for the reference's per-extension import probing."""
    if name not in _feature_cache:
        mod = _FEATURE_MODULES.get(name)
        if mod is None:
            _feature_cache[name] = False
        else:
            import importlib
            try:
                importlib.import_module(mod)
                _feature_cache[name] = True
            except ImportError:
                _feature_cache[name] = False
    return _feature_cache[name]


__all__ = ["__version__", "comm", "has_feature"]
