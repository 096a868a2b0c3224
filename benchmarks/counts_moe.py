"""The yardstick of the sparse-attention expert-decoder cells, counted
from shapes alone as ``counts.py`` counts the others: what a step *has
to* do.  Attention is counted over the (query, key) pairs the selection
keeps, whatever a kernel executes, and the experts over the assignments
the routing is expected to send here; operations that rematerialisation
runs a second time do not count.  Each function has a hand-worked value
in ``tests/benchmark/test_keye_cell.py``.
"""

from __future__ import annotations


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs of one sequence: query t keeps
    min(t + 1, topk) of its t + 1 causal keys."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                    topk: int, layers: int = 1) -> float:
    """Forward and backward of attention proper (QK^T and PV, no
    projections) over the SELECTED pairs only: 2 matmuls forward, 4
    backward, each 2*d FLOPs a pair and head: 12*b*heads*d*pairs a
    layer."""
    return 12.0 * batch * heads * head_dim * selected_pairs(seq, topk) * layers


def indexer_flops(batch: int, heads: int, seq: int, head_dim: int,
                  layers: int = 1) -> float:
    """Forward and backward of the index scores over their causal pairs
    (every causal pair is scored before any is selected): one matmul
    forward, two backward, 2*d FLOPs a pair and head."""
    return 6.0 * batch * heads * head_dim * causal_pairs(seq) * layers


def expert_flops(tokens: int, hidden: int, width: int, top_k: int,
                 held: int, router_width: int, layers: int = 1) -> float:
    """Forward and backward of the held experts' three matrices over
    the assignments expected here (tokens * top_k * held / router_width,
    from shapes, not from a run's routing): 6 FLOPs a parameter and
    assignment."""
    assignments = tokens * top_k * held / router_width
    return 6.0 * assignments * 3 * hidden * width * layers


def step_flops(batch: int, seq: int, hidden: int, layers: int, heads: int,
               kv_heads: int, head_dim: int, width: int, top_k: int,
               held: int, router_width: int, index_heads: int,
               index_head_dim: int, topk: int, vocab: int) -> float:
    """Matmul FLOPs of one training step, forward + backward (6 per
    parameter and token): per layer the fused q/k/v projection, the
    output projection, the indexer's projection and the router; the
    expected assignments' share of the held experts; the untied head
    at every position; attention over the selected pairs; the index
    scores over the causal pairs.  The embedding look-up, the norms,
    the gated activation, the softmaxes, the selection and the indexer's
    objective (whose second pass over the main attention's scores is
    the price of the objective, not model work) are not counted."""
    tokens = batch * seq
    per_layer = (hidden * (heads + 2 * kv_heads) * head_dim
                 + heads * head_dim * hidden
                 + hidden * (index_heads * index_head_dim + index_head_dim
                             + index_heads)
                 + hidden * router_width)
    dense = 6.0 * tokens * (layers * per_layer + hidden * vocab)
    return (dense
            + expert_flops(tokens, hidden, width, top_k, held, router_width,
                           layers)
            + attention_flops(batch, heads, seq, head_dim, topk, layers)
            + indexer_flops(batch, index_heads, seq, index_head_dim, layers))


def xent_bytes(tokens: int, vocab: int, logit_bytes: int = 4) -> float:
    """Bytes the cross-entropy of one step has to move over the logits:
    the forward reads them once, the backward reads them and writes
    their gradient."""
    return 3.0 * tokens * vocab * logit_bytes
