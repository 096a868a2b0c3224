"""Parity package for apex.multi_tensor_apply (SURVEY.md §2.1).

Reference: apex/multi_tensor_apply/multi_tensor_apply.py — a dispatcher
that chunks many CUDA tensors into one kernel launch.  On TPU the analog
is: concatenate leaves (grouped by dtype) into one flat buffer, run one
Pallas grid over it, split back.  XLA's fusion makes the jnp fallback
competitive; the flat path guarantees a single kernel for huge trees.
"""

from apex_tpu.multi_tensor_apply.multi_tensor_apply import (
    MultiTensorApply,
    multi_tensor_applier,
    flatten,
    unflatten,
    flatten_tensors,
    unflatten_tensors,
)
from apex_tpu.multi_tensor_apply.packer import (
    Bucket,
    BucketPlan,
    LeafSpec,
    PackedState,
)

__all__ = [
    "MultiTensorApply",
    "multi_tensor_applier",
    "flatten",
    "unflatten",
    "flatten_tensors",
    "unflatten_tensors",
    "Bucket",
    "BucketPlan",
    "LeafSpec",
    "PackedState",
]
