"""The benchmark's yardstick: chip peaks, and the operations and bytes
each step *has to* do, counted from shapes alone.

Nothing here looks at a compiled program (``cost_analysis`` changes with
the implementation and counts nothing for a Mosaic call): a later PR
that changes how a step is computed is measured against the same
numbers.  Recomputed operations do not count.  Each function has a
hand-worked value in ``tests/benchmark/test_benchmark.py``.
"""

from __future__ import annotations

# Published peaks, keyed by ``jax.devices()[0].device_kind``.  A device
# that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"benchmarks/counts.py: no published peaks for device kind "
            f"{device_kind!r}; add a row with its source")
    return PEAKS[device_kind]


# ---- transformer encoder (BERT as apex_tpu.models.bert builds it) ---------

def attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                    layers: int = 1) -> float:
    """Forward and backward of scaled-dot-product attention proper
    (QK^T and PV, no projections): 2 matmuls forward, 4 backward, each
    2*b*heads*s*s*d FLOPs.  The flash backward's recomputed QK^T is not
    counted."""
    return 6 * 2.0 * batch * heads * seq * seq * head_dim * layers


def bert_step_flops(batch: int, seq: int, hidden: int, layers: int,
                    heads: int, ffn: int, vocab: int) -> float:
    """Matmul FLOPs of one training step, forward + backward (3x the
    forward): per layer QKV (h x 3h), output projection (h x h), two
    feed-forward matmuls (h x ffn), attention proper; and the MLM head
    over the tied embedding (h x vocab) at every position.  Embedding
    look-ups, LayerNorm, GELU and the softmax are not matmuls."""
    tokens = batch * seq
    per_layer = 2.0 * tokens * (3 * hidden * hidden + hidden * hidden
                                + 2 * hidden * ffn)
    head = 2.0 * tokens * hidden * vocab
    dense = 3.0 * (layers * per_layer + head)
    return dense + attention_flops(batch, heads, seq, hidden // heads,
                                   layers)


# ---- ResNet (v1.5 bottleneck, as apex_tpu.models.resnet builds it) --------

def _conv(h, w, cin, cout, k, stride):
    """(FLOPs, out_h, out_w) of a 'same'-padded k x k convolution."""
    oh, ow = -(-h // stride), -(-w // stride)
    return 2.0 * oh * ow * k * k * cin * cout, oh, ow


def resnet_forward_flops(image: int = 224, stage_sizes=(3, 4, 6, 3),
                         width: int = 64, classes: int = 1000) -> float:
    """Convolution and dense FLOPs of one image's forward pass through
    a bottleneck ResNet: 7x7/2 stem, 3x3/2 max-pool, stages of
    1x1 -> 3x3 (strided in the first block of stages 2-4) -> 1x1 with a
    1x1 projection on each stage's first block, global pool, dense."""
    total, h, w = _conv(image, image, 3, width, 7, 2)
    h, w = -(-h // 2), -(-w // 2)                    # max-pool /2
    cin = width
    for i, blocks in enumerate(stage_sizes):
        f = width * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            a, _, _ = _conv(h, w, cin, f, 1, 1)
            b, oh, ow = _conv(h, w, f, f, 3, stride)
            c, _, _ = _conv(oh, ow, f, 4 * f, 1, 1)
            total += a + b + c
            if cin != 4 * f or stride != 1:
                total += _conv(h, w, cin, 4 * f, 1, stride)[0]
            h, w, cin = oh, ow, 4 * f
    return total + 2.0 * cin * classes


def resnet_step_flops(batch: int, **kw) -> float:
    """Forward + backward (3x the forward: one matmul forward, two
    backward per convolution) over the batch."""
    return 3.0 * batch * resnet_forward_flops(**kw)


# ---- optimizer updates ------------------------------------------------------

# bytes per parameter one update has to move, every operand once: the
# gradient read in the model's dtype, f32 masters and each f32 state
# slot read and written, the model-dtype parameter written.  Whatever
# implements the update is measured against this.
_STATE_SLOTS = {"sgd_momentum": 1, "lamb": 2, "adam": 2}


def optimizer_bytes(algorithm: str, n_params: int, model_bytes: int = 2,
                    masters: bool = True) -> float:
    if algorithm not in _STATE_SLOTS:
        raise SystemExit(f"benchmarks/counts.py: no byte count for "
                         f"optimizer {algorithm!r}")
    slots = _STATE_SLOTS[algorithm]
    per = model_bytes                                 # gradient read
    per += 2 * 4 * slots                              # state r+w
    if masters:
        per += 2 * 4 + model_bytes                    # master r+w, model w
    else:
        per += 2 * model_bytes
    return float(per) * n_params
